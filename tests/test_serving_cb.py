"""Continuous-batching serving engine (serve.BatchedServer):

* mid-run admission parity — a request admitted into a freed slot (while
  another request is mid-flight at a non-zero position) produces exactly the
  tokens the same prompt produces served alone, across every cache family
  (GQA KV, MLA absorbed-latent, RWKV recurrent state, hybrid SWA-ring+Mamba);
* paged-KV parity — the block-pool cache (serve/kv_pool.py +
  gqa/mla_decode_paged) produces token-identical output vs the dense
  reference across the same families, including mid-run admission into
  freed slots whose blocks were recycled, and OOM surfacing as deferred
  admission rather than a crash;
* chunked-prefill parity — ``prefill_chunk`` in {1, 4, prompt_len} is
  token-exact vs one-token prefill, with TTFT dropping to
  ``ceil(prompt_len / C)`` steps;
* ``ServeMetrics`` zero-division edges (no finished requests -> 0/None, not
  raise) and JSON round-trip through ``as_dict``/``from_dict``;
* occupancy stays saturated under a Poisson-ish arrival stream;
* per-slot stop handling (max_new_tokens / max_seq) and deterministic rid
  ordering from ``run``;
* sharding decision + fallback bookkeeping (dense slots AND paged block
  pool), and 8-forced-host-device subprocess runs proving the mesh-sharded
  cache paths — dense and paged block pool — match single-device decode
  with token-exact mid-run admission under the mesh;
* ``repro.launch.serve`` CLI smoke (incl. paged + chunked flags).
"""
import copy
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.dist import meshes
from repro.models import model_zoo
from repro.serve.metrics import ServeMetrics
from repro.serve.serving import BatchedServer, Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one arch per cache family: full-KV GQA, absorbed-latent MLA, O(1) recurrent
# RWKV, SWA-ring + Mamba hybrid (MoE is excluded on purpose: capacity-based
# routing couples batch rows, so cross-batch parity is not defined for it)
FAMILIES = ["internlm2-20b", "minicpm3-4b", "rwkv6-3b", "hymba-1.5b"]


def _params(arch, seed=2):
    if arch == "hymba-swa":
        # reduced hymba makes every layer global; force a real SWA segment so
        # the ring-on-blocks path is exercised (window 16 < the test max_seq)
        cfg = dataclasses.replace(get_reduced_config("hymba-1.5b"),
                                  n_global_layers=1)
    else:
        cfg = get_reduced_config(arch)
    params, _ = model_zoo.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


# --------------------------- mid-run admission --------------------------------
@pytest.mark.parametrize("arch", FAMILIES)
def test_midrun_admission_token_exact(arch):
    """The acceptance bar: admission into a freed slot is token-exact vs solo."""
    cfg, params = _params(arch)
    srv = BatchedServer(cfg, params, batch_slots=2, max_seq=32)
    srv.submit(Request(0, [5, 6, 7, 8], 12))  # long: still running at admission
    srv.submit(Request(1, [1, 2], 3))         # short: frees its slot mid-run
    while not any(r.rid == 1 for r in srv.finished):
        srv.step()
    assert all(r is not None and r.rid == 0 for r in srv.active if r), srv.active
    srv.submit(Request(2, [9, 3, 9, 4], 5))   # admitted into B's freed slot
    done = srv.run()
    assert [r.rid for r in done] == [0, 1, 2]
    c_mid = next(r.out for r in done if r.rid == 2)

    solo = BatchedServer(cfg, params, batch_slots=2, max_seq=32)
    solo.submit(Request(2, [9, 3, 9, 4], 5))
    c_solo = next(r.out for r in solo.run() if r.rid == 2)
    assert c_mid == c_solo, (arch, c_mid, c_solo)
    # and the long-running neighbour was not perturbed by the admission
    a_mid = next(r.out for r in done if r.rid == 0)
    ref = BatchedServer(cfg, params, batch_slots=2, max_seq=32)
    ref.submit(Request(0, [5, 6, 7, 8], 12))
    a_solo = next(r.out for r in ref.run() if r.rid == 0)
    assert a_mid == a_solo, (arch, a_mid, a_solo)


def test_slot_reuse_chain_token_exact():
    """Three generations of occupants through the same slot stay exact."""
    cfg, params = _params("internlm2-20b")
    srv = BatchedServer(cfg, params, batch_slots=1, max_seq=24)
    prompts = [[3, 1, 4], [1, 5, 9, 2], [6, 5]]
    for i, p in enumerate(prompts):
        srv.submit(Request(i, list(p), 4))
    done = srv.run()
    assert [r.rid for r in done] == [0, 1, 2]
    for i, p in enumerate(prompts):
        solo = BatchedServer(cfg, params, batch_slots=1, max_seq=24)
        solo.submit(Request(9, list(p), 4))
        assert done[i].out == solo.run()[0].out, i


# --------------------------- paged KV parity ----------------------------------
# a stream with more requests than slots so finished slots free their blocks
# back to the pool and later admissions recycle them (LIFO free list: reuse
# is guaranteed, and stale contents must stay invisible behind the masks)
_PAGED_STREAM = [([5, 6, 7, 8], 9), ([1, 2], 3), ([9, 3, 9, 4], 5),
                 ([2, 7], 4), ([8, 1, 6], 6), ([4, 4, 4, 4, 4], 3)]


def _serve_stream(cfg, params, stream, slots=2, max_seq=24, **kw):
    srv = BatchedServer(cfg, params, batch_slots=slots, max_seq=max_seq, **kw)
    for i, (p, n) in enumerate(stream):
        srv.submit(Request(i, list(p), n))
    return [r.out for r in srv.run()], srv


@pytest.mark.parametrize("arch", FAMILIES + ["hymba-swa"])
def test_paged_vs_dense_token_exact(arch):
    """The tentpole acceptance bar: paged KV decode (block tables, recycled
    blocks, SWA-ring-on-blocks) is token-exact vs the dense reference, with
    mid-run admission into slots whose blocks were freed and re-mapped."""
    cfg, params = _params(arch)
    ref, _ = _serve_stream(cfg, params, _PAGED_STREAM)
    # block_size 5 does not divide max_seq 24 or the ring width 16: partial
    # trailing blocks on both regions are part of what parity pins
    got, srv = _serve_stream(cfg, params, _PAGED_STREAM, kv="paged",
                             block_size=5)
    assert got == ref, arch
    m = srv.metrics
    assert m.finished == len(_PAGED_STREAM)
    if srv.kv_mode == "paged":  # rwkv has no per-token cache: dense fallback
        assert 0 < m.kv_blocks_peak <= m.kv_blocks_total, m.as_dict()
        assert srv._paged.pool.blocks_in_use == 0  # free-on-finish drained
    else:
        assert arch == "rwkv6-3b" and m.kv_blocks_total == 0


def test_paged_oom_defers_admission_and_completes():
    """An undersized pool (half dense capacity) forces deferrals mid-stream;
    every request still finishes token-exact — OOM is backpressure, never a
    crash or corruption."""
    cfg, params = _params("internlm2-20b")
    ref, _ = _serve_stream(cfg, params, _PAGED_STREAM, slots=3)
    got, srv = _serve_stream(cfg, params, _PAGED_STREAM, slots=3, kv="paged",
                             block_size=4, kv_blocks=5)  # dense-equiv is 18
    assert got == ref
    m = srv.metrics
    assert m.finished == len(_PAGED_STREAM)
    assert m.deferrals > 0, "undersized pool must defer at least one admission"
    assert m.kv_blocks_peak <= 5
    # an impossible request (demand > whole pool) fails loudly at submit
    with pytest.raises(ValueError, match="KV blocks"):
        srv.submit(Request(99, list(range(1, 20)), 10))


def test_paged_long_prompt_beyond_dense_slot_budget():
    """The memory story: at equal cache bytes (same total token rows), paged
    admits a prompt longer than a dense slot's whole row. Dense rejects it
    at submit; paged serves it to completion alongside the short stream."""
    cfg, params = _params("internlm2-20b")
    slots, dense_seq = 2, 16
    dense = BatchedServer(cfg, params, batch_slots=slots, max_seq=dense_seq)
    long_prompt = list(range(1, 21))  # 20 tokens >= dense max_seq 16
    with pytest.raises(ValueError, match="max_seq"):
        dense.submit(Request(0, long_prompt, 4))
    # same token-row budget (slots * dense_seq = 32 rows), double the horizon
    srv = BatchedServer(cfg, params, batch_slots=slots, max_seq=2 * dense_seq,
                        kv="paged", block_size=4,
                        kv_blocks=slots * dense_seq // 4)
    srv.submit(Request(0, long_prompt, 4))
    srv.submit(Request(1, [3, 1, 4], 4))
    done = srv.run()
    assert [r.rid for r in done] == [0, 1]
    assert len(done[0].out) == 4
    # and the long request is token-exact vs serving it solo
    solo = BatchedServer(cfg, params, batch_slots=1, max_seq=2 * dense_seq,
                         kv="paged", block_size=4)
    solo.submit(Request(0, list(long_prompt), 4))
    assert solo.run()[0].out == done[0].out


# --------------------------- chunked prefill -----------------------------------
@pytest.mark.parametrize("arch", ["internlm2-20b", "rwkv6-3b", "hymba-swa"])
def test_chunked_prefill_token_exact(arch):
    """C in {1, 4, prompt_len} is token-exact vs one-token prefill — every
    sub-step IS a one-token step with idle rows frozen, so this holds for
    recurrent state (rwkv/mamba) as much as for KV caches."""
    cfg, params = _params(arch)
    ref, _ = _serve_stream(cfg, params, _PAGED_STREAM)
    for c in (1, 4, max(len(p) for p, _ in _PAGED_STREAM)):
        got, _ = _serve_stream(cfg, params, _PAGED_STREAM, prefill_chunk=c)
        assert got == ref, (arch, c)
    # paged x chunked composes
    got, _ = _serve_stream(cfg, params, _PAGED_STREAM, prefill_chunk=4,
                           kv="paged", block_size=5)
    assert got == ref, arch


def test_chunked_prefill_ttft_steps_contract():
    """TTFT in steps is exactly ceil(prompt_len / C): the chunked step
    consumes up to C prompt tokens and emits on the one consuming the
    final prompt token."""
    cfg, params = _params("internlm2-20b")
    prompts = [[7] * 1, [7] * 4, [7] * 5, [7] * 9]
    for c in (1, 4):
        srv = BatchedServer(cfg, params, batch_slots=len(prompts), max_seq=16,
                            prefill_chunk=c)
        for i, p in enumerate(prompts):
            srv.submit(Request(i, list(p), 2))
        done = srv.run()
        assert all(r.steps >= -(-len(r.prompt) // c) for r in done)
        got = sorted(srv.metrics.ttft_steps)
        assert got == sorted(-(-len(p) // c) for p in prompts), (c, got)


def test_invalid_kv_and_chunk_args_rejected():
    cfg, params = _params("rwkv6-3b")
    with pytest.raises(ValueError, match="kv must be"):
        BatchedServer(cfg, params, batch_slots=1, max_seq=8, kv="virtual")
    with pytest.raises(ValueError, match="prefill_chunk"):
        BatchedServer(cfg, params, batch_slots=1, max_seq=8, prefill_chunk=0)
    with pytest.raises(ValueError, match="block_size"):
        BatchedServer(cfg, params, batch_slots=1, max_seq=8, kv="paged",
                      block_size=0)
    # a request generating nothing would reserve zero paged blocks and then
    # write a whole chunk anyway: rejected at submit for every layout
    srv = BatchedServer(cfg, params, batch_slots=1, max_seq=8)
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit(Request(0, [1, 2], 0))
    gq, gp = _params("internlm2-20b")
    paged = BatchedServer(gq, gp, batch_slots=2, max_seq=8, kv="paged",
                          block_size=1, kv_blocks=2, prefill_chunk=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        paged.submit(Request(1, [4], 0))


# ------------------------------ metrics ----------------------------------------
def test_metrics_zero_division_edges():
    """A fresh server (nothing admitted, nothing finished) must report 0/None
    from every derived metric — not raise — and survive as_dict/json."""
    m = ServeMetrics(slots=4)
    assert m.occupancy_pct == 0.0 and m.tok_per_s == 0.0
    assert m.mean_ttft_s is None and m.mean_ttft_steps is None
    assert m.kv_blocks_peak_pct == 0.0
    d = m.as_dict()
    assert d["tok_per_s"] == 0.0 and d["mean_ttft_s"] is None
    json.dumps(d)  # None serializes; nothing raises
    # zero wall clock with tokens (pathological timer) still cannot divide
    m.tokens_generated = 5
    assert m.tok_per_s == 0.0


def test_metrics_as_dict_round_trips_bench_schema():
    """as_dict -> JSON -> from_dict -> as_dict is lossless, so archived
    BENCH_serve.json rollups reload exactly."""
    m = ServeMetrics(slots=2, steps=7, active_slot_steps=11, admitted=3,
                     finished=2, deferrals=1, tokens_generated=9,
                     prompt_tokens=6, wall_s=0.25, kv_blocks_total=8,
                     kv_blocks_peak=5, ttft_s=[0.1, 0.2], ttft_steps=[2, 3])
    d = json.loads(json.dumps(m.as_dict()))
    m2 = ServeMetrics.from_dict(d)
    assert m2 == m
    assert m2.as_dict() == m.as_dict()
    assert d["prefill_tokens"] == 6 and d["decode_tokens"] == 9
    assert d["kv_blocks_peak_pct"] == pytest.approx(62.5)


def test_metrics_prefill_vs_decode_token_split():
    """prompt/prefill tokens count every prompt token fed (chunked or not);
    decode tokens count emissions — the two sum to the slot work done."""
    cfg, params = _params("internlm2-20b")
    for c in (1, 3):
        srv = BatchedServer(cfg, params, batch_slots=1, max_seq=16,
                            prefill_chunk=c)
        srv.submit(Request(0, [5, 6, 7, 8], 3))
        srv.run()
        m = srv.metrics
        assert m.prompt_tokens == 4 and m.tokens_generated == 3, c


# ----------------------- occupancy under a stream ------------------------------
def test_occupancy_saturated_under_poisson_stream():
    cfg, params = _params("rwkv6-3b")
    srv = BatchedServer(cfg, params, batch_slots=3, max_seq=16)
    rng = np.random.default_rng(0)
    rid = 0
    n_total = 9
    while rid < n_total or srv.queue or any(srv.active):
        for _ in range(int(rng.poisson(0.9))):  # Poisson-ish arrivals
            if rid < n_total:
                plen = int(rng.integers(2, 5))
                srv.submit(Request(rid, rng.integers(1, 100, plen).tolist(),
                                   int(rng.integers(3, 7))))
                rid += 1
        if srv.queue or any(srv.active):
            srv.step()
    m = srv.metrics
    assert m.finished == n_total and m.admitted == n_total
    assert m.occupancy_pct >= 60.0, m.as_dict()
    assert m.tokens_generated == sum(len(r.out) for r in srv.finished)
    assert m.tok_per_s > 0 and len(m.ttft_s) == n_total
    # TTFT in steps == prompt length under prefill-as-decode
    by_rid = {r.rid: r for r in srv.finished}
    assert all(s >= 2 for s in m.ttft_steps)
    assert m.mean_ttft_steps == pytest.approx(
        sum(len(by_rid[r].prompt) for r in by_rid) / n_total
    )


def test_continuous_beats_drain_on_steps():
    """Same engine, same stream: drain-then-refill pays the per-wave straggler.

    Alternating 9/3-step requests on 2 slots: drain runs 3 waves of 9 =
    27 steps; continuous keeps the short slot busy and finishes in 21."""
    cfg, params = _params("rwkv6-3b")
    reqs = [Request(i, [1, 2], 8 if i % 2 == 0 else 2) for i in range(6)]
    steps = {}
    for mode in ("continuous", "drain"):
        srv = BatchedServer(cfg, params, batch_slots=2, max_seq=16,
                            admission=mode)
        for r in copy.deepcopy(reqs):
            srv.submit(r)
        srv.run()
        assert srv.metrics.finished == 6
        steps[mode] = srv.metrics.steps
    assert (steps["continuous"], steps["drain"]) == (21, 27), steps


# --------------------------- per-slot stop handling ----------------------------
def test_per_slot_stop_and_max_seq():
    cfg, params = _params("internlm2-20b")
    srv = BatchedServer(cfg, params, batch_slots=3, max_seq=10)
    srv.submit(Request(0, [1, 2], 3))        # stops on max_new_tokens
    srv.submit(Request(1, [1, 2, 3, 4], 50))  # capped by max_seq
    srv.submit(Request(2, [7], 1))           # single-token request
    done = srv.run()
    assert [r.rid for r in done] == [0, 1, 2]
    # prompt 2 + 3 generations, first emitted on the last-prompt-token step
    assert len(done[0].out) == 3 and done[0].steps == 4
    # max_seq cap: 10 positions, 4 prompt tokens -> 7 generations (the first
    # emit happens on the step consuming the last prompt token)
    assert len(done[1].out) == 10 - 4 + 1 and done[1].steps == 10
    assert len(done[2].out) == 1 and done[2].steps == 1
    assert all(r.done for r in done)


def test_run_max_steps_and_rid_order():
    cfg, params = _params("rwkv6-3b")
    srv = BatchedServer(cfg, params, batch_slots=2, max_seq=20)
    srv.submit(Request(0, [1, 2], 9))  # rid 0 finishes AFTER rid 1
    srv.submit(Request(1, [3, 4], 2))
    partial = srv.run(max_steps=2)
    assert partial == [] and srv.metrics.steps == 2
    done = srv.run()
    assert [r.rid for r in done] == [0, 1]  # deterministic despite finish order
    assert [r.rid for r in srv.finished] == [1, 0]


def test_submit_validation_and_encdec_rejected():
    cfg, params = _params("rwkv6-3b")
    srv = BatchedServer(cfg, params, batch_slots=1, max_seq=8)
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit(Request(0, [], 4))
    with pytest.raises(ValueError, match="max_seq"):
        srv.submit(Request(1, list(range(1, 9)), 4))
    with pytest.raises(ValueError, match="admission"):
        BatchedServer(cfg, params, batch_slots=1, max_seq=8, admission="magic")
    ed = get_reduced_config("seamless-m4t-medium")
    with pytest.raises(ValueError, match="decoder-only"):
        BatchedServer(ed, {}, batch_slots=1, max_seq=8)


# ------------------------------- sharding --------------------------------------
def test_sharded_path_decision_and_fallbacks():
    cfg, params = _params("internlm2-20b")  # reduced: n_kv_heads = 2
    srv = BatchedServer(cfg, params, batch_slots=4, max_seq=16)
    mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    assert srv.sharded_path(mesh) == ("gspmd", ("data",), "model")
    # slots not divisible by data axes: replicated + recorded
    srv3 = BatchedServer(cfg, params, batch_slots=3, max_seq=16)
    meshes.clear_fallbacks()
    assert srv3.sharded_path(mesh) == ("gspmd", (), "model")
    assert any(t == "serve_cache" and ax == "batch"
               for t, (ax, _), _ in meshes.fallbacks())
    # head dim not divisible by the model axis
    meshes.clear_fallbacks()
    mesh3 = jax.sharding.AbstractMesh((1, 3), ("data", "model"))
    assert srv.sharded_path(mesh3) == ("gspmd", (), None)
    assert any(t == "serve_cache" and ax == "kv_heads"
               for t, (ax, _), _ in meshes.fallbacks())
    # MLA latent cache has no head dim: model axis shards params only
    mla_cfg, mla_params = _params("minicpm3-4b")
    srv_mla = BatchedServer(mla_cfg, mla_params, batch_slots=4, max_seq=16)
    meshes.clear_fallbacks()
    assert srv_mla.sharded_path(mesh) == ("gspmd", ("data",), None)
    assert any(t == "serve_cache" for t, _, _ in meshes.fallbacks())


def test_sharded_path_paged_block_pool_fallbacks():
    """Paged mode shards the *block pool* dim over data: divisibility is
    checked on num_blocks (not slots), with the same fallback bookkeeping."""
    cfg, params = _params("internlm2-20b")  # reduced: n_kv_heads = 2
    mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    srv = BatchedServer(cfg, params, batch_slots=3, max_seq=16, kv="paged",
                        block_size=4, kv_blocks=16)
    meshes.clear_fallbacks()
    # 3 slots would NOT divide data=4, but 16 blocks do: paged decouples the
    # data axis from the slot count — that is the point of pooling
    assert srv.sharded_path(mesh) == ("gspmd", ("data",), "model")
    assert not meshes.fallbacks()
    # block count not divisible by the data axes: replicated + recorded
    srv10 = BatchedServer(cfg, params, batch_slots=4, max_seq=16, kv="paged",
                          block_size=4, kv_blocks=10)
    meshes.clear_fallbacks()
    assert srv10.sharded_path(mesh) == ("gspmd", (), "model")
    assert any(t == "serve_cache" and ax == "kv_blocks"
               for t, (ax, _), _ in meshes.fallbacks())


def test_degenerate_mesh_parity_in_process():
    """mesh= on a 1-device host mesh must not change the served tokens."""
    cfg = get_reduced_config("internlm2-20b")
    params, specs = model_zoo.init_params(cfg, jax.random.PRNGKey(2))
    prompts = [[5, 6, 7], [1, 2, 9, 4]]

    def serve(mesh, param_specs=None):
        srv = BatchedServer(cfg, params, batch_slots=2, max_seq=20, mesh=mesh,
                            param_specs=param_specs)
        for i, p in enumerate(prompts):
            srv.submit(Request(i, list(p), 5))
        return [r.out for r in srv.run()], srv

    ref, _ = serve(None)
    got, srv = serve(meshes.make_host_mesh(), param_specs=specs)
    assert got == ref
    assert srv.last_sharded_path is not None


# --------------------------- 8-device subprocess -------------------------------
_MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.configs import get_reduced_config
    from repro.dist import meshes
    from repro.models import model_zoo
    from repro.serve.serving import BatchedServer, Request

    assert jax.device_count() == 8
    cfg = get_reduced_config("internlm2-20b")
    params, specs = model_zoo.init_params(cfg, jax.random.PRNGKey(2))
    mesh = meshes.make_host_mesh(model_parallel=2)  # (data 4, model 2)

    # -- 1. teacher-forced per-step logits parity: the sharded cache path
    # (slots over data, kv heads over model) must match single-device decode
    # at the repo's decode tolerance (bf16 activations reorder reductions)
    decode = jax.jit(model_zoo.decode_fn(cfg))
    decode_m = jax.jit(model_zoo.decode_fn(cfg))
    cache = model_zoo.make_cache(cfg, 4, 24)
    with meshes.use_mesh(mesh):
        cache_sh = meshes.tree_shardings(
            model_zoo.cache_specs(cache), cache, mesh,
            rules=meshes.SERVE_CACHE_RULES)
        cache_m = jax.device_put(cache, cache_sh)
        params_m = jax.device_put(
            params, meshes.tree_shardings(specs, params, mesh))
    # cache really is partitioned over (data, model)
    k0 = jax.tree_util.tree_leaves(cache_m)[0]
    assert not k0.sharding.is_fully_replicated, k0.sharding
    rng = np.random.default_rng(5)
    toks = rng.integers(1, cfg.vocab_size, (10, 4)).astype(np.int32)
    # staggered per-slot positions: every row decodes at its own offset
    offsets = jnp.asarray([0, 3, 1, 7], jnp.int32)
    for t in range(toks.shape[0]):
        tok = jnp.asarray(toks[t])
        pos = offsets + t
        logits, cache = decode(params, tok, cache, pos)
        with meshes.use_mesh(mesh):
            logits_m, cache_m = decode_m(params_m, tok, cache_m, pos)
        l = np.asarray(logits[:, : cfg.vocab_size], np.float32)
        lm = np.asarray(logits_m[:, : cfg.vocab_size], np.float32)
        np.testing.assert_allclose(l, lm, rtol=6e-2, atol=6e-2)
    print("SHARDED-DECODE-PARITY-OK")

    # -- 2. mid-run admission stays token-exact inside the sharded path
    def serve(reqs):
        srv = BatchedServer(cfg, params, batch_slots=4, max_seq=24,
                            mesh=mesh, param_specs=specs)
        for rid, prompt, new in reqs:
            srv.submit(Request(rid, list(prompt), new))
        return {r.rid: r.out for r in srv.run()}, srv

    stream = [(0, [5, 6, 7, 8], 12), (1, [1, 2], 3), (2, [8, 8], 4),
              (3, [3, 1, 4, 1], 5), (4, [9, 3, 9, 4], 5)]  # 4 slots, 5 reqs
    got, srv = serve(stream)
    assert srv.last_sharded_path == ("gspmd", ("data",), "model")
    solo, _ = serve([(4, [9, 3, 9, 4], 5)])
    assert got[4] == solo[4], (got[4], solo[4])
    m = srv.metrics
    assert m.admitted == 5 and m.finished == 5 and m.occupancy_pct > 50
    print("SHARDED-ADMISSION-OK")
    """
)


def test_sharded_serving_8_devices_subprocess():
    """8 forced host devices: mesh-sharded KV cache (slots over data, heads
    over model) matches single-device decode; admission exact under mesh."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    for marker in ("SHARDED-DECODE-PARITY-OK", "SHARDED-ADMISSION-OK"):
        assert marker in out.stdout, out.stdout


# --------------------- 8-device subprocess: paged pool -------------------------
_PAGED_MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import get_reduced_config
    from repro.dist import meshes
    from repro.models import model_zoo
    from repro.serve.serving import BatchedServer, Request

    assert jax.device_count() == 8
    cfg = get_reduced_config("internlm2-20b")
    params, specs = model_zoo.init_params(cfg, jax.random.PRNGKey(2))
    mesh = meshes.make_host_mesh(model_parallel=2)  # (data 4, model 2)

    stream = [(0, [5, 6, 7, 8], 12), (1, [1, 2], 3), (2, [8, 8], 4),
              (3, [3, 1, 4, 1], 5), (4, [9, 3, 9, 4], 5)]  # 4 slots, 5 reqs

    def serve(mesh=None, **kw):
        srv = BatchedServer(cfg, params, batch_slots=4, max_seq=24, mesh=mesh,
                            param_specs=specs if mesh is not None else None,
                            **kw)
        for rid, prompt, new in stream:
            srv.submit(Request(rid, list(prompt), new))
        return {r.rid: r.out for r in srv.run()}, srv

    # -- 1. sharded block pool (16 blocks over data=4, kv heads over model=2)
    # matches the single-device paged server and the dense reference, with
    # mid-run admission (5 reqs, 4 slots) recycling freed blocks under mesh
    ref, _ = serve()
    paged_kw = dict(kv="paged", block_size=6, kv_blocks=16, prefill_chunk=2)
    solo, _ = serve(**paged_kw)
    meshes.clear_fallbacks()
    got, srv = serve(mesh=mesh, **paged_kw)
    assert srv.last_sharded_path == ("gspmd", ("data",), "model"), \\
        srv.last_sharded_path
    assert got == solo == ref, (got, solo, ref)
    k0 = jax.tree_util.tree_leaves(srv.cache)[0]
    assert not k0.sharding.is_fully_replicated, k0.sharding
    m = srv.metrics
    assert m.admitted == 5 and m.finished == 5
    assert 0 < m.kv_blocks_peak <= 16
    print("PAGED-SHARD-PARITY-OK")

    # -- 2. block count not divisible by the data axes: fallback recorded,
    # pool replicated, tokens still exact
    meshes.clear_fallbacks()
    got10, srv10 = serve(mesh=mesh, kv="paged", block_size=6, kv_blocks=10)
    assert srv10.last_sharded_path == ("gspmd", (), "model")
    assert got10 == ref
    print("PAGED-SHARD-FALLBACK-OK")
    """
)


def test_sharded_paged_pool_8_devices_subprocess():
    """8 forced host devices: the paged block pool shards over (data, model)
    — blocks over data, kv heads over model — token-exact vs single-device
    paged AND dense serving, with the divisibility fallback recorded when
    the block count does not divide the data axes."""
    # forced host devices are CPU devices: the child never takes the chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _PAGED_MULTI_DEVICE_SCRIPT],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    for marker in ("PAGED-SHARD-PARITY-OK", "PAGED-SHARD-FALLBACK-OK"):
        assert marker in out.stdout, out.stdout


# ------------------------------- CLI smoke -------------------------------------
def test_launch_serve_cli_smoke(capsys):
    from repro.launch import serve as serve_cli

    done = serve_cli.main([
        "--arch", "rwkv6-3b", "--reduced", "--batch", "2", "--requests", "3",
        "--prompt-len", "4", "--max-new", "3",
    ])
    assert len(done) == 3 and all(len(r.out) == 3 for r in done)
    msg = capsys.readouterr().out
    assert "tok/s" in msg and "occupancy" in msg


def test_launch_serve_cli_paged_chunked_smoke(capsys):
    from repro.launch import serve as serve_cli

    done = serve_cli.main([
        "--arch", "internlm2-20b", "--reduced", "--batch", "2", "--requests",
        "3", "--prompt-len", "6", "--max-new", "3", "--kv", "paged",
        "--block-size", "4", "--prefill-chunk", "3",
    ])
    assert len(done) == 3 and all(len(r.out) == 3 for r in done)
    msg = capsys.readouterr().out
    assert "kv=paged" in msg and "blocks" in msg
