"""The fused programs that existed before the recurrent carry became a pytree lower to what they lowered to.

``FINGERPRINTS`` were taken at the parent of PR 30 (commit 39a5b61) with ``fingerprint`` below: the SHA-256 of
the lowered program's text and, should a later JAX print the same program under other names, the three
losses of the first three dispatches to the bit on the CPU.

The two ``.pixels`` cases are their pixel twins (``env=jax_multiroom``, ``cnn_keys=[rgb]``), taken at the
parent of PR 33 (commit 2328e5c) before that PR was written.  It changed their text on purpose (the fused
rollout stores a frame as the env's uint8, lane-dense, and the train phase normalizes what it gathers), so
they pin no digest: the losses are held to the bit, since the same byte reaches the same cast either way.
(Taken under this suite's ``conftest.py``: XLA:CPU splits a convolution's sums by the size of its thread
pool, which ``NPROC=32`` there fixes; a bare ``python`` run of the same program rounds otherwise.)

The ``.tokens`` case is the tiny Trinity phase (``exp=ppo_tokens`` with ``algo/decoder@algo.decoder=tiny``),
taken at the parent of PR 34 (commit fb11605) before that PR touched ``models/decoder.py``: the block around a
mixer became data of the decoder's yaml and the carry gained a third kind of state, and under the defaults the
Trinity yamls leave in force the phase lowers to the parent's text and gives its losses to the bit (both held
when PR 34 was written; the test asks for either, as for the others).
"""

import hashlib

import pytest

from sheeprl_tpu.parallel.fabric import Fabric

COMMON = [
    "env=jax_cartpole", "env.num_envs=4", "algo.rollout_steps=8", "algo.per_rank_batch_size=16", "algo.update_epochs=2",
    "algo.total_steps=96", "algo.run_test=False", "fabric.accelerator=cpu", "fabric.devices=1", "metric.log_level=0",
    "checkpoint.every=1000000", "checkpoint.save_last=False", "print_config=False", "seed=5",
]
PIXELS = ["env=jax_multiroom", "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]"] + COMMON[1:]
TOKENS = [
    "exp=ppo_tokens", "algo/decoder@algo.decoder=tiny", "env.wrapper.vocab_size=64", "env.wrapper.prompt_min=2",
    "env.wrapper.prompt_max=4", "env.wrapper.len_min=24", "env.wrapper.len_max=32", "fabric.precision=32-true",
] + COMMON[1:4] + COMMON[5:]  # update_epochs stays the exp's 1
CASES = {
    "ppo_recurrent.anakin_phase": ["exp=ppo_recurrent", "algo.mlp_keys.encoder=[state]"] + COMMON,
    "ppo.anakin_phase": ["exp=ppo", "algo.mlp_keys.encoder=[state]"] + COMMON,
    "ppo.anakin_phase.pixels": ["exp=ppo"] + PIXELS,
    "a2c.anakin_phase.pixels": ["exp=a2c"] + PIXELS,
    "ppo_recurrent.anakin_phase.tokens": TOKENS,
}
FINGERPRINTS = {
    "ppo_recurrent.anakin_phase": ("295e03695902f42525d77e76f856469841fcf8b19025fc877d60cf4480933e96", "-0x1.069b840000000p+2,0x1.0d9c6e0000000p+4,-0x1.2ec3820000000p-1;-0x1.4c3fd80000000p+1,0x1.dc898c0000000p+2,-0x1.46a50e0000000p-1;-0x1.c2165e0000000p+1,0x1.ace03c0000000p+3,-0x1.43f95e0000000p-1"),
    "ppo.anakin_phase": ("124a0c14a6451bd4888c5a8f10af4dc0ae044467edc94bbe217df49948a54721", "-0x1.cb614c0000000p+1,0x1.d8694c0000000p+3,-0x1.6191ac0000000p-1;-0x1.dd68000000000p+1,0x1.d4426e0000000p+3,-0x1.6039cc0000000p-1;-0x1.a229760000000p+1,0x1.aeb9ca0000000p+3,-0x1.5c9c8a0000000p-1"),
    "ppo.anakin_phase.pixels": (None, "-0x1.7cc8200000000p-5,0x1.ca2fee0000000p-3,-0x1.61e1f80000000p+0;-0x1.6105040000000p-4,0x1.f0dd560000000p-7,-0x1.6cc16a0000000p+0;-0x1.8d8e800000000p-8,0x1.8c85260000000p-7,-0x1.6a3b400000000p+0"),
    "ppo_recurrent.anakin_phase.tokens": ("b4ca807712be8a4532cca09d15053104e3e36d03ae2e0d2284c4696229b40d29", "0x1.95b8320000000p-5,0x1.f20bba0000000p-5,-0x1.096d500000000p+2;-0x1.00c58a0000000p-1,0x1.97d86e0000000p-2,-0x1.093d660000000p+2;0x1.e941ce0000000p-5,0x1.855a920000000p-6,-0x1.094a6c0000000p+2"),
    "a2c.anakin_phase.pixels": (None, "0x1.f01d540000000p+0,0x1.3a4a180000000p-2,0x1.9801540000000p+0;-0x1.8012740000000p+1,0x1.19a6440000000p-1,0x1.e4775c0000000p-1;0x1.999cc80000000p+1,0x1.3122e80000000p-1,0x1.06a1d80000000p+0"),
}


def fingerprint(case, log_dir):
    """(sha256 of the lowered text, the losses of every dispatch as hex floats) of one short run."""
    from sheeprl_tpu.cli import run

    program = case.removesuffix(".pixels").removesuffix(".tokens")

    texts, losses = [], []
    real = Fabric.compile

    class Probe:
        def __init__(self, aot):
            self.aot = aot

        def __getattr__(self, name):
            return getattr(self.aot, name)

        def __call__(self, *args):
            if not texts:
                texts.append(self.aot.lower(*args).as_text())
            out = self.aot(*args)
            losses.append(",".join(float(x).hex() for x in out[4]))
            return out

    def probed(fabric, fn, **kwargs):
        aot = real(fabric, fn, **kwargs)
        return Probe(aot) if kwargs.get("name") == program else aot

    Fabric.compile = probed
    try:
        run(CASES[case] + [f"log_dir={log_dir}"])
    finally:
        Fabric.compile = real
    return hashlib.sha256(texts[0].encode()).hexdigest(), ";".join(losses)


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_is_the_one_the_parent_lowered(case, tmp_path):
    digest, losses = fingerprint(case, tmp_path)
    pinned_digest, pinned_losses = FINGERPRINTS[case]
    assert losses.count(";") == 2  # three dispatches
    assert digest == pinned_digest or losses == pinned_losses, (digest, losses)
