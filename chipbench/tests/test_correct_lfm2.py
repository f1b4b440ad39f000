"""`correct` of the LFM2 cell at rehearsal size on the CPU, in ``test_correct_trinity.py``'s pattern: the plain
reference against the program, and the control (every matmul operand in fp8) and each planted fault of the reference
(a segment's first taps reading nought where the carry's rows belong, taps reaching across an episode's start, half of
every minibatch left out) put in the program's place and judged by the same ``harness.judge`` with the limits of the
configuration's file: each must come out as not correct, the two conv faults by the number that is there for them."""

import os

import pytest

if os.environ.get("JAX_PLATFORMS", "") != "cpu":
    pytest.skip("set JAX_PLATFORMS=cpu: these tests rehearse on the CPU", allow_module_level=True)

from chipbench.tests.test_correct import AGREES, Sound  # noqa: E402

CELL = "lfm2_tokens_longgen"
SEEN_BY = {"control": None, "conv_prefix": "carry_tap_gap", "conv_reset": "reset_tap_gap", "half_batch": "load_gap"}


@pytest.fixture(scope="module")
def sound():
    return Sound(CELL)


def test_reference_agrees_with_the_program(sound):
    correct, compared, numbers, _ = sound.judge()
    assert correct
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert len(gaps) == 7 and max(gaps.values()) < AGREES, gaps
    assert sum(numbers["where"]["value"]["reset_steps"]) > 0  # an episode started inside the three dispatches


@pytest.mark.parametrize("stand_in", sorted(SEEN_BY))
def test_the_control_and_each_planted_fault_are_not_correct(sound, stand_in):
    correct, compared, _, _ = sound.judge(stand_in)
    assert correct is False, compared
    over = [k for k, v in compared.items() if not v["value"] <= v["limit"]]
    assert over and SEEN_BY[stand_in] in over + [None], compared
