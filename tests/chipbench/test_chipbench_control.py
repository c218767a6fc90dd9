"""The control comes out as not correct.

The control is the plain reference put in the program's place at fp8
(e4m3 operands, e5m2 gradients, one scale per tensor), the precision below
the configurations' bf16.  On the chip it was read at each cell's own
sizes (PERF.md gives the readings the limits were set from).  Here it is
read at a small size that a test run holds, with limits set the same way
from CPU readings at that size (program, bf16, 6 seeds: logit_gap at most
0.027; loss_gap at most 0.0030, grad_gap 0.0015, update_gap 0.00043;
control, 6 seeds: logit_gap at least 0.31; loss_gap 0.023, grad_gap
0.0051, update_gap 0.0018): the program passes every number and the
control fails at least one.
"""

import pytest

from chipbench import calibrate, harness
from chipbench_cells import SERVE, TRAIN, make_root

SMALL = {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
         "num_key_value_heads": 4, "head_dim": 64, "num_hidden_layers": 2,
         "vocab_size": 2048, "max_position_embeddings": 64}
LIMITS = {"tiny.serve": {"logit_gap": {"limit": 0.1}},
          "tiny.train": {"loss_gap": {"limit": 0.01}, "grad_gap": {"limit": 0.003},
                         "update_gap": {"limit": 0.001}}}


@pytest.fixture
def small(tmp_path):
    serve = dict(SERVE, batch=4, prompt_len=24, new_tokens=8, max_len=32,
                 temperature=0.0, check_requests=2)
    return make_root(tmp_path, SMALL, serve, dict(TRAIN, seq_len=32), LIMITS)


def fails(cell, readings):
    return [k for k, v in readings.items() if v > cell.limits[k]["limit"]]


@pytest.mark.parametrize("seed", [1, 4])
def test_serving_control_fails(small, seed):
    cell = harness.load_cell("tiny.serve", small)
    d = harness.driver_class("closed_batches")(cell, seed, None, harness.annotator(False))
    out = calibrate.serve(d, seed, control=True)
    assert not fails(cell, out["program"]), out
    assert fails(cell, out["control"]), out


def test_training_control_fails(small):
    cell = harness.load_cell("tiny.train", small)
    d = harness.driver_class("train_steps")(cell, 2, None, harness.annotator(False))
    out = calibrate.train(d, 2, control=True, fault=False)
    assert not fails(cell, out["program"]), out
    assert fails(cell, out["control"]), out
