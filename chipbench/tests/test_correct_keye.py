"""`correct` of the Keye-VL-2.0 cell at rehearsal size on the CPU, in ``test_correct_nemotron3.py``'s pattern: the plain
reference (the selection by sorting every token's index scores in float32, attention under a dense mask) against the
program, and the control (every matmul operand in fp8) and each planted fault of the reference (the newest keys
instead of the indexer's choice, no selection at all, an indexer that takes no gradient, half of every minibatch left
out) put in the program's place and judged by the same ``harness.judge`` with the limits of the configuration's file:
each must come out as not correct, the two selection faults by the number that is there for them."""

import os

import pytest

if os.environ.get("JAX_PLATFORMS", "") != "cpu":
    pytest.skip("set JAX_PLATFORMS=cpu: these tests rehearse on the CPU", allow_module_level=True)

from chipbench.tests.test_correct import AGREES, Sound  # noqa: E402

CELL = "keye_tokens_longctx"
SEEN_BY = {"control": None, "recent_keys": "select_gap", "dense_keys": "select_gap", "no_index_loss": "change_gap",
           "half_batch": "load_gap"}


@pytest.fixture(scope="module")
def sound():
    return Sound(CELL)


def test_reference_agrees_with_the_program(sound):
    correct, compared, numbers, _ = sound.judge()
    assert correct
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert len(gaps) == 7 and gaps["select_gap"] == 0.0 and max(gaps.values()) < AGREES, gaps


@pytest.mark.parametrize("stand_in", sorted(SEEN_BY))
def test_the_control_and_each_planted_fault_are_not_correct(sound, stand_in):
    correct, compared, _, _ = sound.judge(stand_in)
    assert correct is False, compared
    over = [k for k, v in compared.items() if not v["value"] <= v["limit"]]
    assert over and SEEN_BY[stand_in] in over + [None], compared
