"""`correct` at tiny widths on the CPU: each plain reference against the program, the control coming out as
not correct, and runs with the timed path broken underneath coming out as not correct (a step that returns
its state unchanged, planted in the program; half of every batch left out, planted in the reference put in
the program's place; one chip, so there is no exchange to leave out).

The rehearsal (``rehearse=True``) skips the harness's look for a chip and drives everything else of a run:
``cli.run`` under the probes, the window, the captured dispatches, the reference, the limits of the
configuration's file.  One rehearsal per cell is shared by the tests that only swap what stands in the
program's place; the faults planted in the program itself each need a run of their own.
"""

import os

import pytest

if os.environ.get("JAX_PLATFORMS", "") != "cpu":
    pytest.skip("set JAX_PLATFORMS=cpu: these tests rehearse on the CPU", allow_module_level=True)

from chipbench import harness  # noqa: E402

CELLS = ["ppo_anakin_multiroom", "dv3s_forage_coupled"]
AGREES = 5e-3  # float32 against float32: reduction order alone separates the two


def rehearse(workload, seed, sabotage=None):
    return harness.run_cell(workload, seed, 1.0, False, rehearse=True, sabotage=sabotage)


class Sound:
    """One rehearsal of a cell, kept with what the probes copied, to judge other things put in the program's place."""

    def __init__(self, workload):
        self.h = h = harness.Harness(workload, 11, 1.0, False, rehearse=True)
        from sheeprl_tpu.cli import run
        from sheeprl_tpu.config.compose import compose

        overrides = h.overrides()
        h.cfg = compose(overrides).as_dict()
        h.install()
        try:
            run(overrides)
        except harness.WindowClosed:
            pass
        finally:
            h.uninstall()
        self.config = h.spec["config"]
        self.reference = harness.load_module("reference", self.config["reference"])

    def judge(self, stand_in=None):
        """`harness.judge`, as a run ends in it, of the program or of what is put in its place."""
        h = self.h
        snap = h.snap if stand_in is None else h.program.stand_in(h.cfg, h.snap, self.config, stand_in)
        return harness.judge(h.program, h.cfg, snap, self.config, h.compiles_in_window)


@pytest.fixture(scope="module", params=CELLS)
def sound(request):
    return Sound(request.param)


def test_reference_agrees_with_the_program(sound):
    correct, compared, _, _ = sound.judge()
    assert correct
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert gaps and max(gaps.values()) < AGREES, gaps


@pytest.mark.parametrize("stand_in", ["control", "half_batch"])
def test_lower_precision_and_half_a_batch_are_not_correct(sound, stand_in):
    """The reference in the control's precision, and the reference with half of every batch left out, put in the
    program's place and judged as a run is: `correct` must come out false."""
    correct, compared, _, _ = sound.judge(stand_in)
    assert correct is False, compared


class Unchanged:
    """The steady program, made to hand its state back unchanged (its other outputs are the real ones)."""

    def __init__(self, aot):
        self.aot = aot

    def __getattr__(self, name):
        return getattr(self.aot, name)

    def __call__(self, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, tuple(args[:2]))  # the program donates its state
        out = self.aot(*args, **kwargs)
        return kept + tuple(out[2:])


@pytest.mark.parametrize("workload", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(workload):
    steady = harness.load_module("programs", harness.load_cell(workload)["config"]["program"]).STEADY
    result = rehearse(workload, 12, sabotage=lambda name, aot: Unchanged(aot) if name == steady else None)
    assert result["correct"] is False
    assert result["compared"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-6)
