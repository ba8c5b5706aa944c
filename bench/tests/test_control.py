"""The control: the reference at int8 weights and bfloat16 activations,
put in the program's place, fails the check at the tiny size, on three
seeds, while the program passes there."""

import json

import pytest

from bench import calibrate, check
from bench.tests import tiny


@pytest.mark.parametrize("name", ["ddmd.qwen2-0.5b",
                                  "ddmd.h2o-danube-1.8b"])
def test_control_fails_the_program_passes(name):
    cell = tiny.cell(name)
    limits = cell["limits"]
    lines = list(calibrate.calibrate(cell, [21, 22, 23], {21, 22, 23}, 0.5,
                                     require_chip=False))
    for line in lines:
        prog = {k: line["program"][k] for k in limits}
        assert check.verdict(prog, limits), json.dumps(line)
        ctrl = {k: v for k, v in line["control"].items() if k in limits}
        assert not check.verdict(ctrl, {k: limits[k] for k in ctrl}), \
            json.dumps(line)
