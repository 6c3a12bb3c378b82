"""The check that decides `correct`, at a size a test run holds.

A sound run of the tiny cell is correct.  The same run with the timed path
broken underneath is not: once with a served token altered where the decode
step produces it, once with a decode step that leaves the KV cache as it
was.  (The served path runs one sequence on one chip: no batch to halve and
no exchange between chips.)  And the fp8 control, put in the program's
place, fails the tiny configuration's limit.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, spec, weights
from bench.tests.tiny import TINY_CONFIG, tiny_checkout


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny_checkout(tmp_path_factory.mktemp("checkout"), with_src=False)
    return spec.load_cell("tiny.cold", root)


def _run(cell, seed=5):
    return harness.measure(cell, seed, 1.0, False, time.perf_counter(),
                           devices=jax.devices(), compile_cache=False)


def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ttft_p50_s", "setup_s"}
    assert list(out)[-1] == "checks"
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] == TINY_CONFIG["check"]["max_logit_gap"]


def _altered_token(monkeypatch):
    from repro.serving import engine

    decode = engine.Instance.decode

    def altered(self, token):
        logits = decode(self, token)
        # the third step serves the token the model ranks last
        if self._step == 3:
            logits = logits.at[0, jnp.argmin(logits[0])].set(1e4)
        return logits

    monkeypatch.setattr(engine.Instance, "decode", altered)


def _state_unchanged(monkeypatch):
    from repro.serving import engine

    step = engine._paged_decode_step

    def unchanged(params, cfg, token, tables, lengths, k_pages, v_pages,
                  **kw):
        logits, _, _, lengths = step(params, cfg, token, tables, lengths,
                                     jnp.copy(k_pages), jnp.copy(v_pages),
                                     **kw)
        return logits, k_pages, v_pages, lengths  # the new K/V is dropped

    monkeypatch.setattr(engine, "_paged_decode_step", unchanged)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_broken_path_is_not_correct(cell, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(cell)
    assert out["failed"] == 0
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def _greedy(w, c, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        lg = reference.logits(w, c, np.asarray(seq)[None], len(seq) - 1)
        seq.append(int(np.asarray(lg)[0, -1].argmax()))
    return seq


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_limit(seed):
    """The reference's own greedy tokens read a gap of 0; what the fp8
    control puts first at the same positions reads over the limit."""
    c = TINY_CONFIG
    w = weights.make(c, seed)
    rng = np.random.default_rng(seed)
    picked = []
    for _ in range(4):
        prompt = rng.integers(0, c["vocab_size"], 16).astype(np.int32)
        seq = _greedy(w, c, prompt, 48)
        rec = type("Rec", (), {"tokens": tuple(seq[16:])})
        picked.append(harness.Served(0.0, 0.0, 0.0, 0.0, rec, prompt))
    got = harness.check(c, seed, picked, control=True)
    assert got["max_logit_gap"] == 0.0
    assert got["control_max_logit_gap"] > c["check"]["max_logit_gap"]
