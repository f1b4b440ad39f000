"""`correct` of the Nemotron-3 cell at rehearsal size on the CPU, in ``test_correct_lfm2.py``'s pattern: the plain
reference (the Mamba-2 layer as the sequential recurrence) against the program, and the control (every matmul operand
in fp8) and each planted fault of the reference (a state that is not cut at an episode's start, a segment that starts
from nought where the carry's state belongs, every head reading group 0's ``B`` and ``C``, no shared expert, half of
every minibatch left out) put in the program's place and judged by the same ``harness.judge`` with the limits of the
configuration's file: each must come out as not correct, the three state faults by the number that is there for them."""

import os

import pytest

if os.environ.get("JAX_PLATFORMS", "") != "cpu":
    pytest.skip("set JAX_PLATFORMS=cpu: these tests rehearse on the CPU", allow_module_level=True)

from chipbench.tests.test_correct import AGREES, Sound  # noqa: E402

CELL = "nemotron3_tokens_longgen"
# at rehearsal size a dispatch is 8 steps, within which no head forgets: all three state faults show in the states it
# leaves (on the chip, after 256 steps, `ssm_prefix` and `ssm_reset` show in `carry_gap` and `reset_gap` instead: PERF.md)
SEEN_BY = {"control": None, "ssm_reset": "state_gap", "ssm_prefix": "state_gap", "one_bc_group": "state_gap",
           "no_shared": "logprob_gap", "half_batch": "load_gap"}


@pytest.fixture(scope="module")
def sound():
    return Sound(CELL)


def test_reference_agrees_with_the_program(sound):
    correct, compared, numbers, _ = sound.judge()
    assert correct
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert len(gaps) == 8 and {"state_gap", "carry_gap", "reset_gap"} <= set(gaps) and max(gaps.values()) < AGREES, gaps
    assert sum(numbers["where"]["value"]["reset_steps"]) > 0  # an episode started inside the first dispatch


@pytest.mark.parametrize("stand_in", sorted(SEEN_BY))
def test_the_control_and_each_planted_fault_are_not_correct(sound, stand_in):
    correct, compared, _, _ = sound.judge(stand_in)
    assert correct is False, compared
    over = [k for k, v in compared.items() if not v["value"] <= v["limit"]]
    assert over and SEEN_BY[stand_in] in over + [None], compared
