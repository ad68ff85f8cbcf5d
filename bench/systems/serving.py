"""The LM server as the benchmark drives it: open-loop requests through
``BatchedServer``.

Set-up builds the program's model configuration from the file's sizes,
draws the weights on the device from ``--seed`` in one jitted call
(bfloat16, the type they are served in), builds the server, warms its
programs with one short request, and then runs the mix's arrivals for
``ramp_s`` before the window, so the window starts with the slots as full
as the offered load keeps them; the ramp's last step ends by the window's
start, where the window's first request is due. The window keeps submitting
each request at its due time (between steps: a request due during a step
waits for its end, and that wait counts in its latency) and stepping the
server; it closes at the first step boundary after ``--seconds``. Where the
mix gives ``first_token_wait_s``, the server then keeps stepping, with no
new arrivals, until every request due in the window has its first token, at
most that long: its time to first token is then its own, not its age at the
close. Every time is the benchmark's own clock, read when a step returns.

The check runs the plain reference (``bench/configs/<ref>.py``) over a
sample of the finished requests, drawn from the seed, with the longest in
it: for each served token, how far its reference logit lies below the
reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import traffic

# the program's ModelConfig field for each size a configuration may state;
# each is applied where the file states it. A file maps further keys itself
# under ``program_fields`` (file key -> ModelConfig field): the count of
# routed experts, which on a chip's share is the count held here, is one.
WIDTHS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "moe_d_ff",
    "first_k_dense_replace": "first_dense_layers",
}
# groups of a configuration file that the harness reads, not the model
HARNESS_KEYS = {"serving", "assumed", "departures", "not_applied", "published",
                "program_fields"}
SAMPLE_TOKENS = 512  # served tokens the check compares, at least
SAMPLE_MAX = 8  # requests


def _model_config(cfg: dict):
    """The program's model configuration for this file: the family and the
    attention kind of the program's named configuration, every size the
    file states, weights in the file's dtype. A number or a group (such as
    ``rope_scaling``) the file states that reaches no field, and that
    ``departures`` or ``not_applied`` does not give a reason for, is an
    error: the program would run another model than the file states."""
    from repro.configs import get_config

    base = get_config(cfg["program_config"])
    extra = cfg.get("program_fields", {})
    known = {f.name for f in dataclasses.fields(base)}
    wrong = sorted(f"{k} -> {f}" for k, f in extra.items()
                   if k not in cfg or f not in known)
    if wrong:
        raise ValueError(f"{cfg['name']}: program_fields maps a key the file "
                         f"does not state, or to no ModelConfig field: {wrong}")
    fields = {**WIDTHS, **extra}
    declared = (set(cfg.get("departures", {})) | set(cfg.get("not_applied", {}))
                | HARNESS_KEYS)
    ignored = sorted(k for k, v in cfg.items()
                     if isinstance(v, (int, float, dict)) and k not in fields
                     and k not in declared)
    if ignored:
        raise ValueError(
            f"{cfg['name']}: the file states {ignored}, which reach no field "
            "of the program's ModelConfig: map each under program_fields, or "
            "give the reason it is left out under not_applied")
    return dataclasses.replace(
        base, param_dtype=cfg["torch_dtype"],
        **{f: cfg[k] for k, f in fields.items() if k in cfg})


def make_weights(run, mcfg):
    """Every weight leaf of the program's tree, drawn on the device from the
    seed in one jitted call: N(0, std^2) per ``ref.init_std``, cast to the
    served dtype inside the program."""
    import jax
    import jax.numpy as jnp

    from repro.models import model_zoo

    shapes, _ = model_zoo.init_params(mcfg, abstract=True)
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    stds = []
    for path, s in flat:
        names = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        stds.append(run.ref.init_std(names, s.shape, run.config))

    def gen(key):
        out = []
        for i, ((_, s), std) in enumerate(zip(flat, stds)):
            if std == 0.0:
                out.append(jnp.zeros(s.shape, s.dtype))
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                      jnp.float32)
                out.append((z * np.float32(std)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(tdef, out)

    s = int(np.random.SeedSequence(run.seed).generate_state(1)[0]) & 0x7FFFFFFF
    return jax.jit(gen)(jax.random.PRNGKey(s))


def _passes(rec, chunk: int) -> int:
    """Useful forward passes a request has had: prompt positions plus one
    per served token after the first (a slot advances ``chunk`` positions a
    step from its admission; over-generated positions are not useful)."""
    req = rec["req"]
    if req is None or req.admit_s is None:
        return 0
    return min(chunk * req.steps, rec["plen"] + rec["max_new"] - 1)


def setup(run) -> None:
    import jax

    from repro.serve.serving import BatchedServer, Request

    cfg, mix = run.config, run.traffic
    sv = cfg["serving"]
    mcfg = _model_config(cfg)
    t = time.perf_counter()
    params = make_weights(run, mcfg)
    jax.block_until_ready(params)
    run.note(f"weights drawn in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    srv = BatchedServer(mcfg, params, batch_slots=sv["slots"],
                        max_seq=sv["max_seq"], kv=sv["kv"],
                        block_size=sv["block_size"], kv_blocks=sv["kv_blocks"],
                        attn_impl=sv["attn_impl"],
                        prefill_chunk=sv["prefill_chunk"],
                        step_mode=sv["step_mode"], seed=run.seed)
    # warm the step and slot-reset programs with one short request
    warm = Request(rid=-1, prompt=[1] * sv["prefill_chunk"], max_new_tokens=2)
    srv.submit(warm)
    while not warm.done:
        srv.step()
    srv.finished.clear()
    run.note(f"server built and warmed in {time.perf_counter() - t:.3f} s")
    run.state.update(srv=srv, params=params, mcfg=mcfg, chunk=sv["prefill_chunk"],
                     pending=[], live=[], steps=[])
    schedule = traffic.open_loop(mix, run.seed, run.seconds,
                                 cfg["vocab_size"])
    t0 = time.perf_counter() + float(mix["ramp_s"])
    run.state["pending"] = [dict(r, due=t0 + r["due"]) for r in schedule]
    _drive(run, until=t0, end_by=True)


def _drive(run, until: float, tracer=None, trace_s: float = 0.0,
           end_by: bool = False, stop=None) -> None:
    """Submit what is due and step the server until ``until`` has passed;
    with ``end_by``, start no step that would end after ``until`` and wait
    for it instead (the ramp ends on the window's start); with ``stop``, also
    end once ``stop()`` is true."""
    import jax

    from repro.serve.serving import Request

    st = run.state
    srv, pending, live = st["srv"], st["pending"], st["live"]
    traced_until = None
    step_s = 0.0
    while True:
        now = time.perf_counter()
        if now >= until or (stop is not None and stop()):
            break
        if end_by and now + step_s >= until:
            time.sleep(until - now)
            break
        while pending and pending[0]["due"] <= now:
            r = pending.pop(0)
            rec = {"rid": r["rid"], "due": r["due"], "plen": len(r["prompt"]),
                   "max_new": r["max_new_tokens"], "submit": now,
                   "first": None, "last": None, "n_seen": 0, "done": None,
                   "req": Request(rid=r["rid"], prompt=r["prompt"],
                                  max_new_tokens=r["max_new_tokens"])}
            try:
                with jax.profiler.TraceAnnotation("BatchedServer.submit"):
                    srv.submit(rec["req"])
            except ValueError as e:
                rec["error"] = repr(e)
                rec["done"] = now
            run.records.append(rec)
            if "error" not in rec:
                live.append(rec)
        if not live and not srv.queue:
            # nothing to serve: an idle server waits for the next arrival
            nxt = pending[0]["due"] if pending else until
            time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))
            continue
        if tracer is not None and traced_until is None:
            tracer.start()
            st["trace_passes_0"] = {id(r): _passes(r, st["chunk"])
                                    for r in run.records}
            traced_until = time.perf_counter() + trace_s
        with jax.profiler.TraceAnnotation("BatchedServer.step"):
            srv.step()
        step_s = time.perf_counter() - now
        now = time.perf_counter()
        emitted = 0
        for rec in live:
            req = rec["req"]
            n = len(req.out)
            if n > rec["n_seen"]:
                emitted += n - rec["n_seen"]
                rec["n_seen"] = n
                rec["last"] = now
                if rec["first"] is None:
                    rec["first"] = now
            if req.done or req.status in ("CANCELLED_DEADLINE", "REJECTED"):
                rec["done"] = now
        st["live"] = live = [r for r in live if r["done"] is None]
        st["steps"].append((now, emitted, srv.last_admit_s))
        if tracer is not None and now >= traced_until:
            _stop_trace(run, tracer)
            tracer = None
    if tracer is not None and traced_until is not None:  # window ended first
        _stop_trace(run, tracer)


def _stop_trace(run, tracer) -> None:
    tracer.stop()
    run.state["trace_passes_1"] = {id(r): _passes(r, run.state["chunk"])
                                   for r in run.records}


def _queue(srv) -> tuple[int, int]:
    """(requests waiting for a slot, requests in a slot)."""
    return len(srv.queue), sum(r is not None for r in srv.active)


def _await_first_tokens(run, wait_s: float) -> None:
    """After the window's close: submit what was due before it and step the
    server until every request due in the window has its first token (or has
    ended), or ``wait_s`` has passed; ``ttft_until`` is when that ended, the
    time a request still without a first token counts its age to."""
    t0, t1 = run.window_t0, run.window_t1

    def served() -> bool:
        return not any(p["due"] < t1 for p in run.state["pending"]) and all(
            r["first"] is not None or r["done"] is not None
            for r in run.records if t0 <= r["due"] < t1)

    if wait_s > 0:
        _drive(run, until=t1 + wait_s, stop=served)
    run.state["ttft_until"] = time.perf_counter() if wait_s > 0 else t1


def window(run, tracer) -> None:
    st = run.state
    n_steps0 = len(st["steps"])
    run.counters["queued_0"], run.counters["running_0"] = _queue(st["srv"])
    st["passes_0"] = {id(r): _passes(r, st["chunk"]) for r in run.records}
    _drive(run, until=run.window_t0 + run.seconds, tracer=tracer,
           trace_s=float(run.traffic["trace_s"]))
    run.window_t1 = time.perf_counter()
    run.counters["queued_1"], run.counters["running_1"] = _queue(st["srv"])
    st["passes_1"] = {id(r): _passes(r, st["chunk"]) for r in run.records}
    steps = st["steps"][n_steps0:]
    _await_first_tokens(run, float(run.traffic.get("first_token_wait_s", 0.0)))
    due = [r for r in run.records if run.window_t0 <= r["due"] < run.window_t1]
    run.attempted = len(due)
    run.failed = sum(1 for r in due if "error" in r
                     or (r["req"].status in ("CANCELLED_DEADLINE", "REJECTED")))
    late = [r["submit"] - r["due"] for r in due]
    run.counters.update(
        steps=len(steps),
        tokens=sum(e for _, e, _ in steps),
        admit_s=sum(a for _, _, a in steps),
        due=len(due),
        generator_late_max_s=max(late) if late else 0.0,
        generator_late_mean_s=float(np.mean(late)) if late else 0.0,
    )
    run.note(f"queue {run.counters['queued_0']} -> {run.counters['queued_1']}, "
             f"running {run.counters['running_0']} -> "
             f"{run.counters['running_1']}; "
             f"{len(steps)} steps, {run.counters['tokens']} tokens, "
             f"{len(due)} requests due, generator late by "
             f"{run.counters['generator_late_mean_s']:.3f} s mean / "
             f"{run.counters['generator_late_max_s']:.3f} s max, "
             f"KV blocks peak {st['srv'].metrics.kv_blocks_peak}")


def release(run) -> None:
    run.state.pop("srv", None)


# -- the check ------------------------------------------------------------------
LIMITS = {
    # between the program's largest reading over a dozen seeds or more and
    # the control's (float8 weights) smallest, on the chip; see PERF.md
    "served_gap": 0.25,
}


def sample(run) -> list[dict]:
    """Finished requests to check: the one with the most served tokens, then
    others drawn from the seed until ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in run.records if "error" not in r and r["req"].done]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r["req"].out), r["rid"]))
    picked, rest = [done[0]], done[1:]
    order = np.random.default_rng(run.seed).permutation(len(rest))
    for i in order:
        if (sum(len(r["req"].out) for r in picked) >= SAMPLE_TOKENS
                or len(picked) >= SAMPLE_MAX):
            break
        picked.append(rest[i])
    return picked


def readings(run, control: bool = False) -> dict:
    """``served_gap``: the widest gap by which a served token's reference
    logit lies below the reference's best at its position. With ``control``
    also ``control_gap``: the same gap for the token that the reference with
    float8 weights puts first, at the same positions."""
    import jax

    cfg, ref = run.config, run.ref
    params = run.state["params"]
    out = {"served_gap": 0.0, "served_tokens": 0}
    if control:
        out["control_gap"] = 0.0
    for rec in sample(run):
        req = rec["req"]
        toks = list(req.prompt) + list(req.out[:-1])
        rows = np.arange(rec["plen"] - 1, rec["plen"] - 1 + len(req.out))
        lg = np.asarray(ref.logits(params, toks, rows, cfg))
        best = lg.max(axis=1)
        served = lg[np.arange(len(rows)), np.asarray(req.out)]
        out["served_gap"] = max(out["served_gap"], float(np.max(best - served)))
        out["served_tokens"] += len(rows)
        if control:
            lc = np.asarray(ref.logits(params, toks, rows, cfg,
                                       transform=ref.fp8_weights))
            pick = lg[np.arange(len(rows)), lc.argmax(axis=1)]
            out["control_gap"] = max(out["control_gap"],
                                     float(np.max(best - pick)))
    jax.clear_caches()
    return out


def check(run) -> None:
    got = readings(run)
    run.note(f"checked {got['served_tokens']} served tokens")
    if got["served_tokens"] == 0:
        run.compare("finished_requests_checked", 1.0, 0.0)
        return
    run.compare("served_gap", got["served_gap"], LIMITS["served_gap"])
