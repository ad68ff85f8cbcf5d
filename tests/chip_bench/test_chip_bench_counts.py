"""Each roofline's and utilization's operation and byte counts against a hand
count at a small shape, the latency arithmetic, and the traffic generator."""
import types

import numpy as np
import pytest

from benchfix import REPO

PEAKS = {"flops_bf16": 1e3, "hbm_bytes_per_s": 1e3}


def load(kind, name):
    from bench import harness

    return harness.load_module(REPO, kind, name)


def analytics_run(trace_kernel_s=None):
    """Two statements over a table of 8 tuples of 3 features, 4 tuples a
    1 KB page (2 pages): a 2-epoch TRAIN and a projected, filtered PREDICT,
    and a failed statement that counts nothing."""
    cfg = {"n_features": 3, "n_tuples": 8, "tuples_per_page": 4,
           "page_bytes": 1024, "udf": {"merge_coef": 4}}
    records = [
        {"verb": "TRAIN", "sql": "SELECT * FROM dana.m('t');", "tuples": 16,
         "epochs": 2},
        {"verb": "PREDICT", "tuples": 8,
         "sql": "SELECT c0, label FROM dana.predict('m', 't') WHERE c5 > 0;"},
        {"verb": "PREDICT", "tuples": 0, "error": "failed", "sql": "x"},
    ]
    return types.SimpleNamespace(
        config=cfg, records=records, peaks=PEAKS, window_s=10.0,
        ref=load("configs", "sn_logistic"),
        trace_red=None if trace_kernel_s is None else {"kernel_s": trace_kernel_s},
        counters={})


def test_page_bytes_and_glm_flops_by_hand():
    ref = load("configs", "sn_logistic")
    cfg = analytics_run().config
    # 24 tuples: three passes over the 2 pages of 1 KB
    assert ref.page_bytes(cfg, 24) == 3 * 2 * 1024
    assert ref.glm_flops(cfg, "TRAIN", 16) == 16 * 4 * 3
    assert ref.glm_flops(cfg, "PREDICT", 8) == 8 * 2 * 3


def test_strider_decode_roofline_by_hand():
    m = load("metrics", "strider_decode.roofline")
    # TRAIN 2 passes + PREDICT 1 pass over 2 KB of pages: 6,144 B at 1e3 B/s
    run = analytics_run({"strider_decode": 10.0})
    assert m.read(run) == pytest.approx(100.0 * 6.144 / 10.0)
    assert m.read(analytics_run({})) is None  # nothing traced: no reading
    assert m.read(analytics_run()) is None


def test_mfu_analytics_by_hand():
    m = load("metrics", "mfu.analytics")
    # FLOPs 192 + 48 = 240 -> 0.24 s; pages 6,144 B -> 6.144 s: bytes bind
    assert m.read(analytics_run()) == pytest.approx(100.0 * 6.144 / 10.0)
    run = analytics_run()
    run.peaks = {"flops_bf16": 10.0, "hbm_bytes_per_s": 1e3}  # FLOPs bind
    assert m.read(run) == pytest.approx(100.0 * 24.0 / 10.0)


MLA = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
       "kv_lora_rank": 4, "q_lora_rank": 6, "qk_nope_head_dim": 2,
       "qk_rope_head_dim": 2, "v_head_dim": 3, "num_hidden_layers": 1,
       "vocab_size": 10}


def test_mla_flops_and_attention_counts_by_hand():
    ref = load("configs", "minicpm3-4b")
    # weight MACs: 8*6 + 6*2*4 + 8*6 + 2*2*4 + 2*4*3 + 2*3*8 + 3*8*16 = 616;
    # at position 0 one row: scores 2*2*(4+2) + values 2*2*4; head 2*8*10
    assert ref.flops_per_pass(MLA, np.array([0])).tolist() == [1432.0]
    assert ref.flops_per_pass(MLA, np.array([1]))[0] == 1432.0 + 40.0
    f, b = ref.attention_counts(MLA, np.array([0, 3]))
    assert f.tolist() == [40.0, 160.0]
    # rows x (latent + rope) x 2 B + query 2 x 6 x 2 B + output 2 x 4 x 4 B
    assert b.tolist() == [12.0 + 24 + 32, 48.0 + 24 + 32]


def serving_run():
    recs = [object(), object()]
    return types.SimpleNamespace(
        config=dict(MLA, num_hidden_layers=2), records=recs, peaks=PEAKS,
        window_s=2.0, ref=load("configs", "minicpm3-4b"),
        trace_red={"kernel_s": {"paged_attention": 1.0}},
        state={"passes_0": {id(recs[0]): 0}, "passes_1": {id(recs[0]): 2,
                                                          id(recs[1]): 1},
               "trace_passes_0": {id(recs[0]): 1},
               "trace_passes_1": {id(recs[0]): 2}})


def test_mfu_serve_and_paged_attention_by_hand():
    run = serving_run()
    ref = run.ref
    cfg = run.config
    want = (ref.flops_per_pass(cfg, np.arange(0, 2)).sum()
            + ref.flops_per_pass(cfg, np.arange(0, 1)).sum())
    mfu = load("metrics", "mfu.serve")
    assert mfu.window_flops(run) == want
    assert mfu.read(run) == pytest.approx(100 * want / 2.0 / 1e3)
    pa = load("metrics", "paged_attention.roofline")
    # one pass (position 1) in the traced span, two layers
    f, b = ref.attention_counts(cfg, np.array([1]))
    assert pa.counts(run) == (2 * f[0], 2 * b[0])
    assert pa.read(run) == pytest.approx(100 * max(2 * f[0], 2 * b[0]) / 1e3)


def test_latency_percentiles_and_open_loop_ttft():
    from bench import latency

    assert latency.p90(list(range(1, 11))) == pytest.approx(9.9)
    run = types.SimpleNamespace(window_t0=10.0, window_t1=20.0, records=[
        {"due": 9.0, "first": 11.0, "done": 12.0, "last": 12.0, "n_seen": 3},
        {"due": 10.0, "first": 10.5, "done": 15.0, "last": 14.5, "n_seen": 5},
        {"due": 19.0, "first": None, "done": None, "last": None, "n_seen": 0},
    ])
    # due before the window: not counted; no first token: age at the end
    assert latency.ttft_ms(run) == pytest.approx([500.0, 1000.0])
    assert latency.tpot_ms(run) == pytest.approx([500.0, 1000.0])


def test_ttft_follows_requests_past_the_close_where_the_mix_waits():
    """With ``ttft_until`` set (the mix's ``first_token_wait_s``), a first
    token after the window's close counts in full, and a request still
    without one counts its age to ``ttft_until``, not to the close."""
    from bench import latency

    recs = [
        {"due": 12.0, "first": 14.0},  # first token inside the window
        {"due": 18.0, "first": 27.0},  # after the close: all 9 s count
        {"due": 19.0, "first": None},  # none by the wait's end: age then
        {"due": 21.0, "first": 22.0},  # due after the close: not counted
    ]
    run = types.SimpleNamespace(window_t0=10.0, window_t1=20.0, records=recs,
                                state={"ttft_until": 30.0})
    assert latency.ttft_ms(run) == pytest.approx([2000.0, 9000.0, 11000.0])
    run.state = {}  # no wait: each is cut at the close, as before
    assert latency.ttft_ms(run) == pytest.approx([2000.0, 2000.0, 1000.0])


def test_saturate_tails_read_the_latency_arithmetic():
    """Above the knee the tails are per-layer readings with the end-to-end
    tails' arithmetic, and read nothing where no request has a reading."""
    from bench import latency

    recs = [{"due": 10.0 + i, "first": 10.5 + 1.5 * i, "done": 11.0 + 2 * i,
             "last": 11.0 + 2 * i, "n_seen": 2 + i} for i in range(6)]
    run = types.SimpleNamespace(window_t0=10.0, window_t1=20.0, records=recs)
    ttft = load("metrics", "ttft_p90_ms.saturate")
    tpot = load("metrics", "tpot_p90_ms.saturate")
    assert ttft.read(run) == pytest.approx(latency.p90(latency.ttft_ms(run)))
    assert tpot.read(run) == pytest.approx(latency.p90(latency.tpot_ms(run)))
    # ttft 500, 1000, ..., 3000 ms: the exclusive p90 of six lies at rank
    # 6.3, past the last by 0.3 of the last step
    assert ttft.read(run) == pytest.approx(3150.0)
    empty = types.SimpleNamespace(window_t0=10.0, window_t1=20.0, records=[])
    assert ttft.read(empty) is None and tpot.read(empty) is None


def test_open_loop_offers_every_seed_the_same_work():
    from bench import traffic

    mix = {"kind": "open_loop", "rate_per_s": 4.0, "ramp_s": 2.0,
           "prompt": {"median": 64, "sigma": 1.0, "min": 16, "max": 1024},
           "output": {"median": 200, "sigma": 0.7, "min": 16, "max": 1024}}
    a = traffic.open_loop(mix, 1, 10.0, 1000)
    b = traffic.open_loop(mix, 2**31 + 5, 10.0, 1000)
    assert len(a) == len(b) == 48  # 8 in the ramp, 40 in the window
    for x in (a, b):
        assert sum(r["due"] < 0 for r in x) == 8
    for part in (slice(0, 8), slice(8, None)):  # each phase apart
        for key in ("max_new_tokens",):
            assert (sorted(r[key] for r in a[part])
                    == sorted(r[key] for r in b[part]))
        assert (sorted(len(r["prompt"]) for r in a[part])
                == sorted(len(r["prompt"]) for r in b[part]))
    assert [r["max_new_tokens"] for r in a] != [r["max_new_tokens"] for r in b]
    due = [r["due"] for r in a]
    assert due == sorted(due) and due[0] == -2.0 and due[8] == 0.0
    assert max(due) < 10.0
    assert all(0 <= t < 1000 for r in a for t in r["prompt"])
    assert traffic.open_loop(mix, 1, 10.0, 1000) == a  # same seed, same work


def test_statements_cycle_from_a_seeded_start():
    from bench import traffic

    mix = {"kind": "statements", "statements": ["A {table}", "B {udf}"]}
    gen = traffic.statements(mix, 5, {"table": "t", "udf": "u"})
    got = [next(gen) for _ in range(4)]
    assert {g[1] for g in got} == {"A t", "B u"}
    assert got[0][1] == got[2][1] != got[1][1]
    assert len({g[2] for g in got}) == 4  # each statement its own seed


def test_sql_reference_reads_the_mix_statements():
    from bench import sqlref

    st = sqlref.parse_select("SELECT c0, c7, label FROM dana.predict('logit', "
                             "'t') WHERE c1 > 0.5 AND (c2 <= 0.0 OR NOT c3 < 1.0);")
    assert st["verb"] == "PREDICT" and st["aggregates"] is None
    x = np.array([[0, 1.0, 0.0, 0.0], [0, 1.0, 1.0, 0.5], [0, 1.0, 1.0, 2.0],
                  [0, 0.0, 0.0, 2.0]])
    keep = sqlref.where_mask(st["where"], lambda c: x[:, int(c[1:])])
    assert keep.tolist() == [True, False, True, False]
    agg = sqlref.parse_select("SELECT COUNT(*), AVG(prediction) FROM "
                              "dana.predict('logit', 't') WHERE c1 > 0.5;")
    assert agg["aggregates"] == ["COUNT(*)", "AVG(PREDICTION)"]
