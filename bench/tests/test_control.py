"""The lower-precision control at a size a test can hold: the reference
solved in bfloat16, put in the program's place, comes out not correct by
the run's own comparison at the cell's limits, on three seeds, while the
program's float32 solve comes out correct; the same control in float32
comes out correct, so what fails it is the precision, not its sweeps."""
import json

import pytest

from bench import compare, control, run, spec

SEEDS = [11, 2**31 + 3, 77]
CELL = "pagerank-kron19.solve"


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(tiny_root, capsys, seed):
    c = spec.load_cell(CELL, tiny_root)
    gen = spec.module("graphs", c.config["generator"], tiny_root)
    ref = spec.module("reference", c.config["app"], tiny_root)
    assert run.main(["--workload", CELL, "--seed", str(seed),
                     "--seconds", "0.5"], root=tiny_root,
                    require_tpu=False) == 0
    program = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert program["correct"] is True
    inst = gen.generate(c.config, seed)
    low = control.judge(inst, c.config, ref, ref.control(inst, c.config))
    assert compare.correct(low) is False
    same = control.judge(inst, c.config, ref,
                         ref.control(inst, c.config, dtype="float32"))
    assert compare.correct(same) is True


def test_control_script_reports_not_correct(tiny_root, capsys):
    assert control.main(["--workload", CELL, "--seeds", str(SEEDS[0])],
                        root=tiny_root) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["solve_finished"]["value"] == 1.0
