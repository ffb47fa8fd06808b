"""The train driver's comparison at toy size on the CPU: the plain float32
reference against ``TrainStep`` over the first three steps; the faults a
training cell can have, each planted under the timed path and seen to come
out not correct; and the control."""
import io
import json

import pytest

import bench_toy
from benchmark import harness

CELL = "toy-gpt.toy-train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_toy.make_root(tmp_path_factory.mktemp("bench_train"))


def run(root, seed=2 ** 31 + 11):
    out = io.StringIO()
    harness.run_cell(root, CELL, seed, 1.0, False, need_chip=False, out=out,
                     err=io.StringIO())
    return json.loads(out.getvalue().strip().splitlines()[-1])


def driver(root, seed):
    cell = harness.Cell(root, CELL)
    return cell.driver().Driver(cell, seed, 0.3,
                                harness.Tracer(False, "", 1.0))


def test_program_agrees_and_control_reads_wider(root):
    """bf16 activations against float32 ones move a loss of 6.2 by 1e-4 at
    most and a leaf's gradient norm by under 2% (measured 3e-5 and 0.6%).
    The change of the parameters carries the recipe's stochastic rounding,
    drawn from two different streams: at toy width (leaves of 128 elements)
    that alone is worth several percent, so the bound here is 0.25; at the
    real widths the leaves are 16 times larger or more. The fp8 control has
    to read wider than the program on the first gradient."""
    d = driver(root, 21)
    d.setup()
    d.window()
    d.release()
    v = d.check()["compared"]
    assert v["loss_gap.1"]["value"] < 1e-3
    assert v["loss_gap.3"]["value"] < 1e-3
    assert v["grad_norm_gap"]["value"] < 0.02
    assert v["change_norm_gap"]["value"] < 0.25
    fp8 = d.control("fp8")
    print(v, fp8)
    assert fp8["grad_vector_gap"] > 3 * v["grad_vector_gap"]["value"]
    half = d.control("half_batch")
    assert half["grad_norm_gap"] > 10 * v["grad_norm_gap"]["value"]


def test_sound_run_is_correct(root):
    last = run(root)
    assert last["correct"] is True, last["compared"]
    assert last["metrics"]["train_tok_s"]["value"] > 0
    assert last["attempted"] > 0 and last["failed"] == 0


def test_state_left_unchanged_is_not_correct(root, monkeypatch):
    from paddle_tpu.jit import TrainStep

    orig = TrainStep._pure_step

    def frozen(self, params, states, buffers, *rest):
        loss, _p, _s, bufs = orig(self, params, states, buffers, *rest)
        return loss, list(params), list(states), bufs

    monkeypatch.setattr(TrainStep, "_pure_step", frozen)
    last = run(root)
    assert last["correct"] is False
    # nothing moved: the change reads 1 by the measure
    assert last["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(root, monkeypatch):
    import paddle_tpu.nn.functional as F

    orig = F.cross_entropy

    def first_half(logits, labels, *a, **kw):
        n = logits.shape[0] // 2
        return orig(logits[:n], labels[:n], *a, **kw)

    monkeypatch.setattr(F, "cross_entropy", first_half)
    last = run(root)
    assert last["correct"] is False
    c = last["compared"]["grad_norm_gap"]
    assert c["value"] > c["limit"]
