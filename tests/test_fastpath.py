"""Data-plane fast paths (DESIGN.md §10): tensor-granular loading through the
host Model Store and the sync-free paged decode loop.

Equivalence is pinned hard: the fast-path decode must match the pre-refactor
(legacy) step bit-for-bit, fused `decode_many` must match per-instance
decode bit-for-bit, and the sync-free property is proven by TRACING a decode
step with the device-resident state abstracted — any host sync (the legacy
`int(lengths[0])` or block-table read-back) concretizes a tracer and raises.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, all_configs
from repro.models import build_model
from repro.serving.engine import Engine


def small_cfg():
    cfg = all_configs()["llama3.2-1b"].smoke()
    return dataclasses.replace(cfg, num_layers=2, vocab_size=512)


def mk_engine(cap=256 * 1024 * 1024, **kw):
    return Engine(cap, **kw)


def mk_batch(model, B, S, seed=0):
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=S, global_batch=B,
                                kind="prefill")
    return model.make_batch(jax.random.PRNGKey(seed), shape)


def mk_instance(cfg, batch, lengths=None):
    eng = mk_engine()
    eng.register("m", cfg)
    eng.load("m")
    inst = eng.start_instance("m", num_pages=64)
    logits = inst.prefill(batch, lengths=lengths)
    return eng, inst, logits


# ---------------------------------------------------------------- load path
def test_warm_load_materializes_zero_leaves():
    """After a release, a fully-warm load touches no leaf: no init_fn call,
    no host materialization, no h2d traffic — the fast path's whole point."""
    eng = mk_engine(64 * 1024 * 1024)
    eng.register("m", small_cfg())
    rep = eng.load("m")
    cold = eng.last_load
    assert cold.leaves_materialized == len(eng.models["m"].records)
    assert cold.bytes_h2d == rep.bytes_transferred > 0
    assert cold.chunks_h2d >= cold.tensors_h2d == len(eng.models["m"].records)
    eng.release("m")
    rep2 = eng.load("m")
    warm = eng.last_load
    assert rep2.reuse_fraction == 1.0
    assert warm.leaves_materialized == 0
    assert warm.bytes_h2d == 0 and warm.tensors_h2d == 0


def test_partial_miss_transfers_only_missed_bytes_without_reinit():
    """Evicting part of a model must reload exactly the missed tensors from
    the host store — bytes moved track the store's plan, and init_fn is
    never re-run (zero leaves materialized)."""
    eng = mk_engine(64 * 1024 * 1024)
    eng.register("m", small_cfg())
    eng.load("m")
    eng.release("m")
    records = eng.models["m"].records
    dropped = records[: len(records) // 3]
    for r in dropped:
        eng.store._evict(r.fingerprint)
    eng.sync_evictions()
    rep = eng.load("m")
    stats = eng.last_load
    assert rep.bytes_transferred == sum(r.nbytes for r in dropped)
    assert stats.bytes_h2d == rep.bytes_transferred
    assert stats.tensors_h2d == len(dropped)
    assert stats.leaves_materialized == 0  # host store already had every leaf


def test_chunked_transfer_pipeline_roundtrip():
    """Large tensors split into row chunks with a bounded in-flight window;
    the reassembled device buffers are exact."""
    from repro.serving.engine import ChunkedTransfer, DataLoadStats

    rng = np.random.default_rng(0)
    big = rng.standard_normal((64, 1024)).astype(np.float32)  # 256 KB
    tiny = rng.standard_normal((3,)).astype(np.float32)
    xfer = ChunkedTransfer(chunk_bytes=16 * 1024, depth=2)
    stats = DataLoadStats()
    out = xfer.transfer([("big", big), ("tiny", tiny)], stats)
    assert np.array_equal(np.asarray(out["big"]), big)
    assert np.array_equal(np.asarray(out["tiny"]), tiny)
    assert stats.tensors_h2d == 2
    assert stats.bytes_h2d == big.nbytes + tiny.nbytes
    assert stats.chunks_h2d == -(-big.nbytes // (16 * 1024)) + 1


def _drop_device_copies(eng, model_id="m"):
    eng.drop_device_copies(model_id)


def test_three_tier_load_matrix():
    """Cold (store-only) / warm (host) / hot (device-pool) loads: the
    three-way byte counters partition the model exactly, and init_fn never
    re-runs once the hierarchy holds the leaves (DESIGN.md §11)."""
    cfg = small_cfg()
    eng = Engine(64 * 1024 * 1024, host_cache_bytes=0)  # spill-everything cap
    eng.register("m", cfg)
    rep = eng.load("m")
    total = rep.bytes_total
    first = eng.last_load
    assert first.leaves_materialized == len(eng.models["m"].records)
    # while loading/active the records are pinned: host tier holds them all
    assert eng.host_store.nbytes() == total

    # COLD: release spills everything (cap 0); drop device buffers too
    _drop_device_copies(eng)
    assert eng.host_store.nbytes() == 0
    assert eng.persistent_store.nbytes() == total
    rep_cold = eng.load("m")
    cold = eng.last_load
    assert cold.leaves_materialized == 0  # init_fn ran once, EVER
    assert (cold.bytes_device_hit, cold.bytes_host_hit, cold.bytes_store) \
        == (0, 0, total)
    assert cold.tensors_store == len(eng.models["m"].records)
    assert cold.bytes_h2d == total  # promoted bytes still cross h2d
    # the returned LoadReport agrees with the data plane: every byte came up
    # from the store tier, and the modeled time is priced at store_bw
    assert (rep_cold.bytes_from_store, rep_cold.bytes_from_host) == (total, 0)
    assert rep_cold.load_seconds == eng.store.costs.load_time_tiered(0, total)

    # HOT: everything device-resident — no tier moves any byte
    eng.load("m")
    hot = eng.last_load
    assert (hot.bytes_device_hit, hot.bytes_host_hit, hot.bytes_store) \
        == (total, 0, 0)
    assert hot.bytes_h2d == 0 and hot.leaves_materialized == 0

    # WARM: ample host cap keeps the working set host-resident
    wide = Engine(64 * 1024 * 1024, host_cache_bytes=4 * total)
    wide.register("m", cfg)
    wide.load("m")
    _drop_device_copies(wide)
    wide.load("m")
    warm = wide.last_load
    assert (warm.bytes_device_hit, warm.bytes_host_hit, warm.bytes_store) \
        == (0, total, 0)
    assert warm.leaves_materialized == 0 and warm.store_seconds == 0.0


def test_partial_spill_splits_host_and_store_bytes():
    """A host cap below the model size spills the LRU tail; the next load's
    counters split exactly across the host and store tiers."""
    cfg = small_cfg()
    eng = Engine(64 * 1024 * 1024)
    eng.register("m", cfg)
    rep = eng.load("m")
    total = rep.bytes_total
    eng.host_store.capacity_bytes = total // 2  # shrink the cap mid-flight
    _drop_device_copies(eng)  # unpin -> LRU spill down to the new cap
    assert 0 < eng.host_store.nbytes() <= total // 2
    spilled = eng.persistent_store.nbytes()
    assert spilled == total - eng.host_store.nbytes()
    eng.load("m")
    s = eng.last_load
    assert s.bytes_store == spilled
    assert s.bytes_host_hit == total - spilled
    assert s.bytes_device_hit == 0 and s.leaves_materialized == 0
    assert s.bytes_h2d == total


def test_warm_load_wall_time_no_regression_vs_two_tier():
    """The tiering refactor must not slow the PR 2 warm path: a host-hit
    load on a capped (but sufficient) engine takes no longer than on the
    unbounded two-tier engine, within generous noise bounds."""
    import time

    cfg = small_cfg()

    def warm_seconds(**kw):
        eng = Engine(64 * 1024 * 1024, **kw)
        eng.register("m", cfg)
        total = eng.load("m").bytes_total
        best = float("inf")
        for _ in range(3):
            _drop_device_copies(eng)
            t0 = time.perf_counter()
            eng.load("m")
            best = min(best, time.perf_counter() - t0)
        s = eng.last_load
        assert s.bytes_host_hit == total and s.bytes_store == 0
        return best

    two_tier = warm_seconds()
    tiered = warm_seconds(host_cache_bytes=1 << 30)
    assert tiered <= two_tier * 3 + 0.05, (tiered, two_tier)


def test_loading_model_is_pinned_against_concurrent_spill():
    """While model A is active, loading B over a tight host cap must spill
    B's own (unpinned-after-release) bytes or overflow — never evict A's
    pinned host copies out from under a future partial reload."""
    cfg = small_cfg()
    eng = Engine(128 * 1024 * 1024, host_cache_bytes=0)
    eng.register("a", cfg)
    eng.register("b", dataclasses.replace(cfg, num_layers=3))
    total_a = eng.load("a").bytes_total
    recs_a = eng.models["a"].records
    # A active: every A record pinned host-side
    assert all(eng.host_store.pinned(r.fingerprint) for r in recs_a)
    assert eng.host_store.nbytes() == total_a
    eng.load("b")  # B's load spills B's bytes (cap 0) but never A's
    assert all(r.fingerprint in eng.host_store for r in recs_a)
    eng.release("b")
    assert all(r.fingerprint in eng.host_store for r in recs_a)
    eng.release("a")  # last unpin: A spills under the zero cap
    assert eng.host_store.nbytes() == 0
    assert all(r.fingerprint in eng.persistent_store for r in recs_a)


def test_register_seed_is_stable_digest():
    """Default init seeds must not depend on PYTHONHASHSEED: two engines in
    (conceptually) different processes must agree on default params."""
    import zlib

    e1, e2 = mk_engine(), mk_engine()
    cfg = small_cfg()
    e1.register("m", cfg)
    e2.register("m", cfg)
    e1.load("m")
    e2.load("m")
    leaves1 = jax.tree.leaves(e1.params_of("m"))
    leaves2 = jax.tree.leaves(e2.params_of("m"))
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(leaves1, leaves2))
    # and the seed is the documented digest, not hash()
    assert zlib.crc32(b"m") & 0xFFFF == zlib.crc32("m".encode()) & 0xFFFF


# ------------------------------------------------- prefetch pipeline (§12)
def test_prefetch_join_overlaps_store_read():
    """A hint issued a lead window before the load pays the store read in
    the background: the joining load sees the promoted bytes as host hits,
    total store traffic is unchanged (overlap, not avoidance), and wall
    time drops by the hidden part of the read."""
    import time

    cfg = small_cfg()
    eng = Engine(256 << 20, host_cache_bytes=0)
    eng.register("m", cfg)
    total = eng.load("m").bytes_total
    eng.persistent_store.store_bw = total * 10.0  # full read ~ 0.1 s

    eng.drop_device_copies("m")
    reads0 = eng.persistent_store.bytes_read
    t0 = time.perf_counter()
    eng.load("m")
    cold = time.perf_counter() - t0
    assert eng.persistent_store.bytes_read - reads0 == total

    eng.drop_device_copies("m")
    reads0 = eng.persistent_store.bytes_read
    eng.prefetch("m")
    time.sleep(0.15)  # the queueing/init window a placement hint buys
    t0 = time.perf_counter()
    rep = eng.load("m")
    warm = time.perf_counter() - t0
    s = eng.last_load
    assert s.leaves_materialized == 0
    assert s.bytes_prefetched + s.bytes_store == total  # traffic identical
    assert s.bytes_prefetched > 0
    assert eng.persistent_store.bytes_read - reads0 == total
    assert rep.bytes_transferred == total  # h2d still moves every byte
    assert warm < cold  # the hidden read no longer extends the load
    assert eng.prefetcher.joins == 1


def test_duplicate_hints_collapse_onto_one_job():
    cfg = small_cfg()
    eng = Engine(256 << 20, host_cache_bytes=0)
    eng.register("m", cfg)
    total = eng.load("m").bytes_total
    eng.persistent_store.store_bw = total * 10.0
    eng.drop_device_copies("m")
    reads0 = eng.persistent_store.bytes_read
    j1 = eng.prefetch("m")
    j2 = eng.prefetch("m")  # duplicate hint must not double-read the store
    assert j1 is j2
    eng.load("m")
    assert eng.persistent_store.bytes_read - reads0 == total


def test_join_bypasses_unstarted_job_behind_other_hints():
    """A load whose hint is still QUEUED behind another model's throttled
    promotion must not wait for reads it never asked for: the un-started
    job is withdrawn and the load falls back to the inline store path —
    never slower than an unhinted load."""
    cfg = small_cfg()
    eng = Engine(256 << 20, host_cache_bytes=0)
    eng.register("a", cfg)
    eng.register("b", dataclasses.replace(cfg, num_layers=3))
    total_a = eng.load("a").bytes_total
    total_b = eng.load("b").bytes_total
    eng.persistent_store.store_bw = total_a * 4.0  # a's read ~ 0.25 s
    eng.drop_device_copies("a")
    eng.drop_device_copies("b")
    eng.prefetch("a")  # the worker starts on this immediately
    jb = eng.prefetch("b")  # still queued behind a's throttled read
    rep = eng.load("b")
    s = eng.last_load
    assert jb.cancelled and jb.done.is_set()
    assert jb.bytes_promoted == 0  # withdrawn before any read
    assert s.bytes_prefetched == 0 and s.bytes_store == total_b
    assert rep.bytes_transferred == total_b
    rep_a = eng.load("a")  # a's own job was started: joined normally
    sa = eng.last_load
    assert sa.bytes_prefetched + sa.bytes_store == total_a
    assert rep_a.bytes_transferred == total_a


def test_cancel_prefetch_releases_hint_pin():
    """An abandoned hint must not leave the model pinned forever: cancel
    stops the promotion and the bytes become spillable again."""
    cfg = small_cfg()
    eng = Engine(256 << 20, host_cache_bytes=0)
    eng.register("m", cfg)
    eng.load("m")
    eng.drop_device_copies("m")
    eng.prefetch("m")
    assert "m" in eng._host_pins  # hint holds the pin while in flight
    eng.cancel_prefetch("m")
    assert "m" not in eng._host_pins
    # whatever the worker promoted before the cancel re-spilled on unpin
    assert eng.host_store.nbytes() == 0
    eng.load("m")  # and a later unhinted load still resolves everything
    assert eng.last_load.leaves_materialized == 0


def test_rehint_after_completed_job_transfers_pin_ownership():
    """A second hint replacing a completed-but-never-joined job must inherit
    its pin ownership — cancelling the second hint releases the pin the
    FIRST hint took (nothing leaks)."""
    cfg = small_cfg()
    eng = Engine(256 << 20, host_cache_bytes=0)
    eng.register("m", cfg)
    eng.load("m")
    eng.drop_device_copies("m")
    j1 = eng.prefetch("m")
    j1.done.wait()  # first hint's promotion completes, job never joined
    j2 = eng.prefetch("m")
    assert j2 is not j1 and j2.owns_pin  # ownership carried forward
    eng.cancel_prefetch("m")
    assert "m" not in eng._host_pins  # the original hint's pin released
    assert eng.host_store.nbytes() == 0  # and its bytes re-spilled (cap 0)


def test_close_quiesces_in_flight_promotion():
    """close() must stop the worker mid-job, not just drain the queue: no
    store mutations may land after it returns."""
    import time

    cfg = small_cfg()
    eng = Engine(256 << 20, host_cache_bytes=0)
    eng.register("m", cfg)
    total = eng.load("m").bytes_total
    eng.drop_device_copies("m")
    eng.persistent_store.store_bw = total * 0.5  # full read ~ 2 s
    job = eng.prefetch("m")
    t0 = time.perf_counter()
    eng.close()  # returns after at most the in-flight tensor, not the job
    assert time.perf_counter() - t0 < 5.0
    assert job.done.is_set()
    nb = eng.host_store.nbytes()
    time.sleep(0.2)
    assert eng.host_store.nbytes() == nb  # quiesced: nothing moved after


def test_engine_close_stops_prefetch_worker():
    cfg = small_cfg()
    eng = Engine(256 << 20, host_cache_bytes=0)
    eng.register("m", cfg)
    eng.load("m")
    eng.drop_device_copies("m")
    eng.prefetch("m").done.wait()
    eng.close()
    assert eng.prefetcher._thread is None
    job = eng.prefetch("m")  # hints after close degrade to pin-only no-ops
    assert job.done.is_set() and job.bytes_promoted == 0
    eng.load("m")  # and loads still resolve everything inline
    assert eng.last_load.leaves_materialized == 0
    eng.close()  # idempotent


def test_engine_keep_alive_ages_host_tier_between_loads():
    """With the keep-alive knob set, a released model's host copies expire
    after idling past the TTL: the next load promotes them from the store
    tier again — the churn the prefetch pipeline exists to hide."""
    cfg = small_cfg()
    eng = Engine(256 << 20, host_keep_alive_s=120.0)
    eng.register("m", cfg)
    total = eng.load("m").bytes_total
    eng.drop_device_copies("m")  # released, but TTL keeps it host-resident
    eng.load("m")
    assert eng.last_load.bytes_host_hit == total
    eng.drop_device_copies("m")
    for fp in list(eng.host_store._last_access):  # idle past the TTL
        eng.host_store._last_access[fp] -= 300.0
    eng.load("m")
    s = eng.last_load
    assert s.bytes_store == total and s.bytes_host_hit == 0
    assert s.leaves_materialized == 0  # aged out, never re-materialized


# ------------------------------------------------------------- decode: equiv
def test_fast_decode_matches_legacy_bit_for_bit():
    cfg = small_cfg()
    model = build_model(cfg)
    batch = mk_batch(model, B=2, S=30)
    _, fast, lf = mk_instance(cfg, batch)
    _, legacy, ll = mk_instance(cfg, batch)
    assert bool(jnp.array_equal(lf, ll))
    tok = jnp.argmax(lf, -1).astype(jnp.int32)
    for step in range(20):  # crosses a block boundary (T=16) along the way
        a = fast.decode(tok)
        b = legacy.decode_legacy(tok)
        assert bool(jnp.array_equal(a, b)), f"step {step} diverged"
        tok = jnp.argmax(a, -1).astype(jnp.int32)
    # fast path refreshed its tables only on block-mapping steps
    assert fast.table_uploads < 20 / 2


def test_fused_decode_many_matches_per_instance_bit_for_bit():
    cfg = small_cfg()
    model = build_model(cfg)
    ba, bb = mk_batch(model, 2, 24, seed=7), mk_batch(model, 2, 24, seed=9)

    def run(fused: bool):
        eng = mk_engine()
        eng.register("m", cfg)
        eng.load("m")
        ia = eng.start_instance("m", num_pages=64)
        ib = eng.start_instance("m", num_pages=64)
        la, lb = ia.prefill(ba), ib.prefill(bb)
        ta = jnp.argmax(la, -1).astype(jnp.int32)
        tb = jnp.argmax(lb, -1).astype(jnp.int32)
        outs = []
        for _ in range(6):
            if fused:
                oa, ob = eng.decode_many([(ia, ta), (ib, tb)])
            else:
                oa, ob = ia.decode(ta), ib.decode(tb)
            outs.append((oa, ob))
            ta = jnp.argmax(oa, -1).astype(jnp.int32)
            tb = jnp.argmax(ob, -1).astype(jnp.int32)
        return outs

    for (fa, fb), (sa, sb) in zip(run(fused=True), run(fused=False)):
        assert bool(jnp.array_equal(fa, sa))
        assert bool(jnp.array_equal(fb, sb))


def test_mixed_length_batch_matches_per_sequence_reference():
    """Per-sequence lengths (the all-equal-length assumption is gone): a
    mixed-length paged batch must match each sequence decoded alone through
    the model's ring-cache reference path."""
    cfg = small_cfg()
    model = build_model(cfg)
    B, S = 3, 32
    lens = [32, 17, 25]
    batch = mk_batch(model, B, S)
    eng, inst, logits = mk_instance(cfg, batch, lengths=lens)
    params = eng.params_of("m")

    ring = {}
    for b, L in enumerate(lens):
        sub = {k: v[b : b + 1, :L] for k, v in batch.items()}
        rl, rc = jax.jit(lambda p, bt: model.prefill(p, bt, cache_cap=64))(
            params, sub)
        assert float(jnp.max(jnp.abs(logits[b] - rl[0, -1]))) == 0.0
        ring[b] = (jnp.argmax(rl[:, -1], -1).astype(jnp.int32), rc)

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for step in range(8):
        out = inst.decode(tok)
        for b, L in enumerate(lens):
            rtok, rc = ring[b]
            rlog, rc = jax.jit(model.decode)(
                params, rtok, jnp.full((1,), L + step, jnp.int32), rc)
            err = float(jnp.max(jnp.abs(out[b] - rlog[0])))
            assert err < 5e-2, f"seq {b} step {step}: {err}"
            ring[b] = (jnp.argmax(rlog, -1).astype(jnp.int32), rc)
        tok = jnp.argmax(out, -1).astype(jnp.int32)
    inst.finish()


def test_prefill_lengths_share_one_program_and_match_reference_bitwise():
    """Two instances prefill other lengths at one padded (B, S): the forward
    is traced once (lengths are traced values, the model a static one equal
    across instances), and each sequence's logits are bitwise those of the
    per-sequence jitted `model.prefill`."""
    from repro.obs import Tracer
    from repro.obs import jit as obs_jit

    # a vocabulary no other test compiles, so the first prefill traces
    cfg = dataclasses.replace(small_cfg(), vocab_size=448)
    model = build_model(cfg)
    B, S = 2, 40
    batch = mk_batch(model, B, S, seed=3)
    eng = mk_engine()
    eng.register("m", cfg)
    eng.load("m")
    params = eng.params_of("m")
    tracer = Tracer()
    uninstall = obs_jit.install(tracer)
    try:
        outs = []
        for lens in ([40, 23], [9, 33]):
            inst = eng.start_instance("m", num_pages=64)
            outs.append((lens, inst.prefill(batch, lengths=lens)))
            inst.finish()
    finally:
        uninstall()
    traces = [e for e in tracer.events() if e.name == "jit.trace"
              and "_prefill_forward" in e.args["fun"]]
    assert len(traces) == 1
    for lens, logits in outs:
        for b, n in enumerate(lens):
            sub = {k: v[b : b + 1, :n] for k, v in batch.items()}
            rl, _ = jax.jit(lambda p, bt: model.prefill(p, bt, cache_cap=64))(
                params, sub)
            assert bool(jnp.array_equal(logits[b], rl[0, -1])), (lens, b)
    eng.close()


def test_same_model_instances_release_is_refcounted():
    """Finishing ONE of several same-model instances must not deactivate the
    model in the store — the survivor's weights would become evictable
    mid-decode."""
    cfg = small_cfg()
    model = build_model(cfg)
    batch = mk_batch(model, 2, 24)
    eng = mk_engine()
    eng.register("m", cfg)
    eng.load("m")
    ia = eng.start_instance("m", num_pages=64)
    ib = eng.start_instance("m", num_pages=64)
    la, lb = ia.prefill(batch), ib.prefill(batch)
    ia.finish()
    assert "m" in eng.store.active_models  # ib still live: stays pinned
    out = ib.decode(jnp.argmax(lb, -1).astype(jnp.int32))
    assert jnp.all(jnp.isfinite(out))
    ib.finish()
    assert "m" not in eng.store.active_models  # last instance released


# -------------------------------------------------------- decode: sync-free
def _trace_step(inst, decode_fn, tok):
    """Trace one decode step with every device-resident operand abstracted.

    Any device→host read in the step (the legacy `int(lengths[0])` sync or
    the block-table `np.array` round trip) concretizes a tracer and raises —
    so successful tracing PROVES the step issues zero host syncs."""

    def fn(tok, lengths, tables, kp, vp):
        inst._lengths, inst._tables = lengths, tables
        inst.slab.k_pages, inst.slab.v_pages = kp, vp
        return decode_fn(tok)

    return jax.eval_shape(fn, tok, inst._lengths, inst._tables,
                          inst.slab.k_pages, inst.slab.v_pages)


def test_decode_issues_zero_host_syncs():
    cfg = small_cfg()
    model = build_model(cfg)
    batch = mk_batch(model, B=3, S=32)
    _, inst, logits = mk_instance(cfg, batch, lengths=[32, 17, 25])
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = _trace_step(inst, inst.decode, tok)
    assert out.shape == (3, cfg.padded_vocab)


def test_legacy_decode_is_not_sync_free():
    """The pre-refactor step must FAIL the same trace (sanity check that the
    sync detector actually detects)."""
    cfg = small_cfg()
    model = build_model(cfg)
    batch = mk_batch(model, B=2, S=30)
    _, inst, logits = mk_instance(cfg, batch)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    with pytest.raises(Exception, match="[Tt]racer|[Cc]oncret"):
        _trace_step(inst, inst.decode_legacy, tok)


def test_decode_loop_passes_d2h_transfer_guard():
    """Belt and braces: the whole decode loop (including the block-boundary
    crossing that maps new KV blocks) runs under a device→host transfer
    guard."""
    cfg = small_cfg()
    model = build_model(cfg)
    batch = mk_batch(model, B=2, S=30)
    _, inst, logits = mk_instance(cfg, batch)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(20):
            tok = jnp.argmax(inst.decode(tok), -1).astype(jnp.int32)
    inst.finish()


# ------------------------------------------------------- observability plane
def test_real_plane_trace_exports_loadable_perfetto_json(tmp_path):
    """DESIGN.md §18: an Engine with a tracer attached emits the full
    cold-start span family on perf_counter walls — store.read, per-chunk
    h2d, init, profile, load, prefill.dispatch, fused decode steps — and
    the export
    is valid Trace Event Format JSON (what ui.perfetto.dev loads)."""
    import json

    from repro.obs import FlightRecorder, Tracer, write_chrome_trace

    tracer = Tracer(flight=FlightRecorder())
    # host_cache_bytes=0 spills every leaf to the store tier on release;
    # dropping the device copies too makes the SECOND load fully cold, so
    # it exercises the store.read promotion path
    eng = mk_engine(host_cache_bytes=0, tracer=tracer)
    eng.register("m", small_cfg())
    eng.load("m")
    _drop_device_copies(eng)
    eng.load("m")
    model = build_model(small_cfg())
    inst = eng.start_instance("m", num_pages=64)
    tok = jnp.argmax(inst.prefill(mk_batch(model, 2, 24)), -1)
    for _ in range(3):
        tok = jnp.argmax(eng.decode_many([(inst, tok.astype(jnp.int32))])[0],
                         -1)
    eng.close()

    by_name = {}
    for ev in tracer.events():
        by_name.setdefault(ev.name, []).append(ev)
    for name in ("store.read", "h2d", "h2d.chunk", "init", "profile",
                 "load", "prefill.dispatch"):
        assert name in by_name, f"cold-start phase {name} never traced"
    assert len(by_name["decode.step"]) == 3
    cold, reload_ = by_name["load"]
    assert cold.track == f"eng:{eng.engine_id}"
    # engine-internal phases nest inside their load span on the same clock
    (init,) = by_name["init"]
    assert cold.begin <= init.begin and init.end <= cold.end + 1e-6
    (read,) = by_name["store.read"]
    assert reload_.begin <= read.begin and read.end <= reload_.end + 1e-6
    assert read.args["bytes"] > 0 and read.args["retries"] == 0
    assert cold.args["pred"] > 0  # priced for the cost-model cross-check

    path = write_chrome_trace(tracer.events(), str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" for e in evs)  # named thread lanes
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans and all(e["dur"] >= 0 for e in spans)
