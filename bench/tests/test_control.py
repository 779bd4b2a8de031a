"""The control: the plain reference computed in float8 (one step below the
bfloat16 the configurations state), put in the program's place, fails the
limits, while the program passes them (at the --tiny sizes, held to the
rehearsal's limits in bench/tiny.json)."""

import calibrate
import harness
import spec
from conftest import BENCH

ROOT = BENCH.parent


def over(reading, limits):
    return [k for k in ("loss_gap", "grad_gap", "update_gap")
            if reading[k] > limits[k]]


def test_control_fails_and_program_passes():
    c = spec.cell(spec.load_benchmark(ROOT), ROOT, "tinyllama.steady")
    model, _ = harness.sized(c, True)
    prog = harness.Program(c, model, c.chips)
    out = calibrate.one_seed(harness, c, model, prog, 31337, control=True,
                             fault=False, tiny=True)
    program, control = out
    assert program["kind"] == "program" and control["kind"] == "control_fp8"
    assert program["rows_bad"] == 0
    held = harness.limits(c, True)
    assert over(program, held) == []
    assert over(control, held) != []
