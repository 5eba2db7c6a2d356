"""The control (the reference one precision step down, answering in the
program's place) runs through the cell's own window and checks, and
comes out not correct, at a size a CPU test can hold."""

import pytest

from bench import control, run
from conftest import small

SEED = 5 ** 13


@pytest.mark.parametrize("name", ["schema-indexed-reads",
                                  "keyonly-feed-ingest"])
def test_control_is_not_correct(spec, name):
    cfg = run.load_config(next(w for w in spec["workloads"]
                               if w["name"] == name)["config"])
    ov = small(cfg, messages=1 << 16)
    line = run.run_cell(spec, name, SEED, 3.0, False, overrides=ov,
                        cell_cls=control.ControlCell)
    assert not line["correct"]
    wrong = line["checks"]["wrong_answers"]
    assert wrong["value"] > wrong["limit"], line["checks"]
    assert line["info"]["answers_checked"] > 0
