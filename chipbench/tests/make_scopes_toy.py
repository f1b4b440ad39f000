"""Records ``data/scopes_toy.xplane.pb``, the small trace ``test_scopes.py`` reads: run it on the chip.

    JAX_COMPILATION_CACHE_DIR=<an empty directory> python3 chipbench/tests/make_scopes_toy.py <out dir>

A toy train step (one matmul under ``wm.encoder``, a scan of eight matmuls under ``wm.rssm``, one matmul
under no scope and a loss under ``wm.heads`` that the compiler fuses into it, differentiated) runs six iterations under the program's
own spans: ``iter``, ``update.dispatch``, ``exec.step``.  Every second iteration sleeps 4 ms under
``log.flush``; the others sleep 12 ms under no span but the iteration's.  The compile cache has to be
empty: an entry written without the scopes would be served without them.
"""

import glob
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from sheeprl_tpu.telemetry.spans import SPANS  # noqa: E402


def loss_fn(w, x):
    with jax.named_scope("wm.encoder"):
        h = jnp.tanh(x @ w["enc"])
    with jax.named_scope("wm.rssm"):
        h, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w["rec"]), None), h, None, length=8)
    y = h @ w["out"]  # under no scope
    with jax.named_scope("wm.heads"):
        return jnp.mean(jnp.square(y))


@jax.jit
def step(w, x):
    loss, grads = jax.value_and_grad(loss_fn)(w, x)
    return jax.tree.map(lambda p, g: p - 1e-3 * g, w, grads), loss


def main(out_dir: str) -> None:
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    w = {name: jax.random.normal(k, (1024, 1024), jnp.bfloat16) * 0.03 for name, k in zip(("enc", "rec", "out"), keys)}
    x = jax.random.normal(keys[3], (2048, 1024), jnp.bfloat16)
    w, loss = step(w, x)  # compiled before the profiler records
    jax.block_until_ready(loss)
    trace_dir = Path(out_dir) / "trace"
    jax.profiler.start_trace(str(trace_dir))
    for i in range(6):
        SPANS.iteration(i)
        with SPANS.span("update.dispatch"):
            with SPANS.span("exec.step", phase=False):
                w, loss = step(w, x)
            jax.block_until_ready(loss)
        if i % 2 == 0:
            with SPANS.span("log.flush", phase=False):
                time.sleep(0.004)
        else:
            time.sleep(0.012)
    SPANS.end_iteration()
    jax.profiler.stop_trace()
    (found,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    shutil.copy(found, Path(out_dir) / "scopes_toy.xplane.pb")
    print("wrote", Path(out_dir) / "scopes_toy.xplane.pb", Path(found).stat().st_size, "bytes; device", jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
