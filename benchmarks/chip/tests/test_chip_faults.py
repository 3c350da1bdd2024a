"""A run with its timed path broken underneath must come out not correct.

Each test skips only the harness's look for a chip: it drives the rest of
a run (``run.execute``: set-up, window, check, result line) at smoke
size on the CPU, with one fault planted where the timed path produces
its answer. The cells here have no exchange between chips, so that fault
has no test.
"""
import time

import jax.numpy as jnp
import jax

import smoke
from benchmarks.chip import readings
from benchmarks.chip import run as chip_run

SEED = 2 ** 31 + 4242


def execute(cell, fault):
    t0 = time.perf_counter()
    return chip_run.execute(cell, SEED, 2.0, False, fault=fault,
                            since=lambda: time.perf_counter() - t0)


def serve_cell():
    c = smoke.cell("qwen3-1.7b.chat")
    # smoke logits spread ~0.2 (vs ~1 at full width): a sound run gaps
    # ~1e-3 here, a wrong token ~0.3
    c.config["check"]["max_logit_gap"] = 0.05
    return c


def test_sound_serving_run_is_correct():
    assert execute(serve_cell(), None)["correct"] is True


def test_altered_token_is_caught():
    def alter(eng):
        tick = eng._tick_fn
        V = eng.model.cfg.vocab_size

        def bad(params, cache, tokens, active):
            nxt, cache = tick(params, cache, tokens, active)
            return jnp.where(active[:, None], (nxt + 1) % V, nxt), cache
        eng._tick_fn = bad
    line = execute(serve_cell(), alter)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > 0.05


def train_cell():
    c = smoke.cell("qwen3-1.7b-train1.pretrain")
    # smoke-size readings: sound runs read loss 2e-5, grad 3e-3, update
    # 1e-3; half a batch reads loss 4e-3 and grad 9e-2
    c.config["check"].update(loss_gap=1e-3, grad_gap=0.03, update_gap=0.03)
    return c


def test_sound_training_run_is_correct():
    assert execute(train_cell(), None)["correct"] is True


def test_unchanged_state_is_caught():
    def frozen(step):
        def bad(state, batch):
            _, met = step(jax.tree_util.tree_map(jnp.copy, state), batch)
            return state, met
        return bad
    line = execute(train_cell(), frozen)
    assert line["correct"] is False


def test_half_batch_is_caught():
    line = execute(train_cell(), readings.half_batch)
    assert line["correct"] is False
