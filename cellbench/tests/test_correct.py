"""``correct`` has to come out true for the program and false for the
control and for each planted fault, at a size a test run can hold.

The control is the reference in fp8 put in the program's place; the
faults break the timed path underneath a whole run (the harness's look
for a chip skipped): a step that returns its state unchanged, half of the
batch left out, the exchange between chips left out, a token altered
where it is produced.
"""

import time

import pytest

from cellbench import check, loop_serve, loop_train, manifest, run
from cellbench.reference import gpt2
from cellbench.tests import tiny

SEED = 2 ** 31 + 1234


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")))


def _run(root, cell, seconds=1.0):
    return run.execute(cell, SEED, seconds, False, root=root,
                       require_tpu=False)


def test_train_run_is_correct(root):
    r = _run(root, "tiny.tiny-train")
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, SEED])
def test_train_control_is_not_correct(root, seed):
    cell = manifest.cell("tiny.tiny-train", root)
    out = loop_train.run(cell, seed, 0.2, None, time.perf_counter(),
                         run.Compiles(), control=True)
    limits = cell.limits["limits"]
    ok, _ = check.verdict(out["readings"], limits)
    assert ok
    for fault in ("_control", "_half_batch"):
        readings = dict(out["readings"][fault], compiles_in_window=0,
                        failed_steps=0)
        ok, checks = check.verdict(readings, limits)
        assert not ok, (fault, checks)


def test_fault_state_returned_unchanged(root, monkeypatch):
    from cellbench.program import Trainer

    def advance(self, batch):
        _, metrics = self.step(
            __import__("jax").tree.map(lambda x: x.copy(), self.state),
            batch, None)
        return metrics                       # the new state is dropped

    monkeypatch.setattr(Trainer, "advance", advance)
    r = _run(root, "tiny.tiny-train", 0.2)
    assert not r["correct"]
    # by the comparison's measure an unmoved leaf reads 1
    assert r["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(root, monkeypatch):
    from cellbench.program import Trainer

    feed = Trainer.feed
    monkeypatch.setattr(Trainer, "feed",
                        lambda self, rows: feed(self, rows[: len(rows) // 2]))
    r = _run(root, "tiny.tiny-train", 0.2)
    assert not r["correct"]
    assert r["checks"]["first_grad_norm_gap"]["value"] > \
        10 * r["checks"]["first_grad_norm_gap"]["limit"]


def test_fault_exchange_between_chips_left_out(root, monkeypatch):
    import apex_tpu.resilience as resilience

    r = _run(root, "tiny.tiny-train-dp2tp2", 0.2)
    assert r["correct"], r["checks"]
    monkeypatch.setattr(resilience, "sync_data_parallel_grads",
                        lambda grads, *a, **k: grads)
    r = _run(root, "tiny.tiny-train-dp2tp2", 0.2)
    assert not r["correct"]


@pytest.mark.parametrize("cell", ["tiny.tiny-closed", "tiny.tiny-open"])
def test_serve_run_is_correct(root, cell):
    r = _run(root, cell, 2.0)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["checks"]["greedy_logit_gap"]["value"] is not None


# At this width (64) and vocabulary (512) a seed can leave fp8's first
# token equal to the reference's at all ~150 sampled positions (3 seeds of
# 7 tried read 0); these three are seeds on which it differs. The chip's
# cells compare thousands of positions over 50k rows: see PERF.md.
@pytest.mark.parametrize("seed", [1, 7, SEED])
def test_serve_control_is_not_correct(root, seed):
    cell = manifest.cell("tiny.tiny-closed", root)
    out = loop_serve.run(cell, seed, 3.0, None, time.perf_counter(),
                         run.Compiles(), control=True)
    limit = cell.limits["limits"]["greedy_logit_gap"]
    assert out["readings"]["greedy_logit_gap"] <= limit
    assert out["readings"]["_control"]["greedy_logit_gap"] > limit


def test_fault_token_altered_where_it_is_produced(root, monkeypatch):
    import apex_tpu.serving.engine as engine

    sample = engine._sample_tokens

    def altered(logits, *a):
        return (sample(logits, *a) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "_sample_tokens", altered)
    r = _run(root, "tiny.tiny-closed", 1.5)
    assert not r["correct"]
    assert r["checks"]["greedy_logit_gap"]["value"] > \
        10 * r["checks"]["greedy_logit_gap"]["limit"]


def test_fp8_control_rounds_forward_and_backward():
    import jax
    import jax.numpy as jnp

    x = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    assert float(jnp.max(jnp.abs(gpt2.fp8_e4m3(x) - x))) > 0
    g = jax.grad(lambda a: jnp.sum(gpt2._mm(a, x, gpt2.fp8)))(x)
    g0 = jax.grad(lambda a: jnp.sum(gpt2._mm(a, x, None)))(x)
    assert g.shape == g0.shape
    assert 0 < float(jnp.max(jnp.abs(g - g0))) < 0.5
