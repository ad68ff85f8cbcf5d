"""Share of the window the statements' thread spent tracing, lowering,
compiling or loading compiled programs: the time covered by the program's
``jax.trace``, ``jax.lower``, ``jax.compile`` and ``jax.cache_load`` spans
(JAX's own durations, kept by ``repro.obs``) on the threads that ran the
window's ``sql.statement`` spans, over the window. Covered time, not a
sum: a cache load runs inside a compile."""
from bench import spans

JIT = ("jax.trace", "jax.lower", "jax.compile", "jax.cache_load")


def read(run):
    clipped = spans.window(run)
    if clipped is None:
        return None
    tids = {s.tid for s, _, _ in clipped if s.name == "sql.statement"}
    if not tids:
        return None
    jit = [(a, b) for s, a, b in clipped if s.name in JIT and s.tid in tids]
    return 100.0 * spans.union_seconds(jit) / run.window_s
