"""`correct` of the Trinity cell at rehearsal size on the CPU, in ``test_correct.py``'s pattern: the plain reference
against the program, and the control (every matmul operand in fp8) and each planted fault of the reference (the window
ignored, the shared expert left out, half of every minibatch left out) put in the program's place and judged by the same
``harness.judge`` with the limits of the configuration's file: each must come out as not correct."""

import os

import pytest

if os.environ.get("JAX_PLATFORMS", "") != "cpu":
    pytest.skip("set JAX_PLATFORMS=cpu: these tests rehearse on the CPU", allow_module_level=True)

from chipbench import harness  # noqa: E402
from chipbench.tests.test_correct import AGREES, Sound  # noqa: E402

CELL = "trinity_tokens_longgen"


@pytest.fixture(scope="module")
def sound():
    return Sound(CELL)


def test_reference_agrees_with_the_program(sound):
    correct, compared, _, _ = sound.judge()
    assert correct
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert len(gaps) == 6 and max(gaps.values()) < AGREES, gaps


@pytest.mark.parametrize("stand_in", ["control", "window", "no_shared", "half_batch"])
def test_the_control_and_each_planted_fault_are_not_correct(sound, stand_in):
    correct, compared, _, _ = sound.judge(stand_in)
    assert correct is False, compared
    over = [k for k, v in compared.items() if not v["value"] <= v["limit"]]
    assert over, compared
