"""A run whose timed path is broken underneath comes out not correct: for each
fault a cell can have, the benchmark's own check catches it. (No cell runs
on more than one chip, so no exchange between chips can be left out.)"""
import numpy as np
import pytest

from benchfix import run_tiny


def _state_unchanged_train(monkeypatch):
    from repro.core.engine import Engine

    orig = Engine.run_chunk

    def run_chunk(self, models, pages, layout, use_kernel=None):
        _, gnorms = orig(self, models, pages, layout, use_kernel)
        return models, gnorms  # the step hands back its input state

    monkeypatch.setattr(Engine, "run_chunk", run_chunk)


def _half_batch_train(monkeypatch):
    from repro.core.engine import Engine

    orig = Engine.run_chunk

    def run_chunk(self, models, pages, layout, use_kernel=None):
        # half of each chunk's tuples left out; the update divides as before
        return orig(self, models, pages[: max(1, len(pages) // 2)], layout,
                    use_kernel)

    monkeypatch.setattr(Engine, "run_chunk", run_chunk)


def _half_batch_scan(monkeypatch):
    from repro.db import scoring

    orig = scoring._scan_chunks

    def scan_chunks(heap, pool, chunk_pages, run_chunk):
        # each chunk's second half of pages left out of the scan
        def half(pages):
            return run_chunk(pages[: max(1, len(pages) // 2)])

        return orig(heap, pool, chunk_pages, half)

    monkeypatch.setattr(scoring, "_scan_chunks", scan_chunks)


def _answer_altered_scan(monkeypatch):
    from repro.db.scoring import PredictScan

    orig = PredictScan.finalize

    def finalize(self, *a, **k):
        res = orig(self, *a, **k)
        if res.predictions is not None and len(res.predictions):
            res.predictions = np.array(res.predictions)
            res.predictions[len(res.predictions) // 2] += 1e-3
        if res.aggregates is not None:
            res.aggregates = dict(res.aggregates)
            res.aggregates["count(*)"] += 1
        return res

    monkeypatch.setattr(PredictScan, "finalize", finalize)


def _column_swapped_scan(monkeypatch):
    from repro.db import scoring

    orig = scoring._column_index

    def column_index(name, layout):
        # the projected decode reads c8 where the statement selects c7
        return orig("c8" if name == "c7" else name, layout)

    monkeypatch.setattr(scoring, "_column_index", column_index)


def _token_altered_chat(monkeypatch):
    from repro.serve.serving import BatchedServer

    orig = BatchedServer.step

    def step(self):
        orig(self)
        for req in self.finished:  # a served token changed where it is made
            if req.out and not getattr(req, "_altered", False):
                req.out[-1] = (req.out[-1] + 1) % self.cfg.vocab_size
                req._altered = True

    monkeypatch.setattr(BatchedServer, "step", step)


def _state_unchanged_chat(monkeypatch):
    from repro.models import attention

    orig = attention.mla_decode_paged

    def mla_decode_paged(p, x, cache, *a, **k):
        out, _ = orig(p, x, cache, *a, **k)
        return out, {"c": cache["c"], "kr": cache["kr"]}  # KV never written

    monkeypatch.setattr(attention, "mla_decode_paged", mla_decode_paged)


FAULTS = {
    "train-state-unchanged": ("tiny_logistic.train", _state_unchanged_train),
    "train-half-batch": ("tiny_logistic.train", _half_batch_train),
    "scan-half-batch": ("tiny_logistic.scan", _half_batch_scan),
    "scan-answer-altered": ("tiny_logistic.scan", _answer_altered_scan),
    "scan-column-swapped": ("tiny_logistic.scan", _column_swapped_scan),
    "chat-token-altered": ("tiny_mla.chat", _token_altered_chat),
    "chat-state-unchanged": ("tiny_mla.chat", _state_unchanged_chat),
    "saturate-token-altered": ("tiny_mla.saturate", _token_altered_chat),
    "saturate-state-unchanged": ("tiny_mla.saturate", _state_unchanged_chat),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    workload, plant = FAULTS[fault]
    plant(monkeypatch)
    res = run_tiny(tiny_root, workload, monkeypatch)
    assert res["correct"] is False, res["compared"]
    assert any(c["value"] > c["limit"] for c in res["compared"].values())
