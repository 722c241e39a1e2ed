"""With the timed path broken underneath, a run's ``correct`` comes out
false: a fold step that returns its state unchanged, half of the log left
out, an answer altered where it is produced, and (for the service) a
stale answer under a fresh snapshot claim."""
import dataclasses

import pytest

from rehearse import rehearse
from test_rehearsal import TINY


def _wrap_streaming(monkeypatch, change):
    from repro.core import engine

    real = engine.run_streaming

    def broken(kernel, chunks):
        return real(*change(kernel, chunks))

    monkeypatch.setattr(engine, "run_streaming", broken)


def state_unchanged(monkeypatch):
    def change(kernel, chunks):
        calls = {"n": 0}

        def update(state, carry, chunk):
            calls["n"] += 1
            if calls["n"] == 2:             # the second fold step is lost
                return state, carry
            return kernel.update(state, carry, chunk)

        return dataclasses.replace(kernel, update=update), chunks
    _wrap_streaming(monkeypatch, change)


def half_left_out(monkeypatch):
    def change(kernel, chunks):
        return kernel, (c for i, c in enumerate(chunks) if i % 2 == 0)
    _wrap_streaming(monkeypatch, change)


def answer_altered(monkeypatch):
    from repro.dataset import engines

    real = engines._collect_many

    def broken(*args, **kwargs):
        out = real(*args, **kwargs)
        d = out.results["dfg"]
        results = dict(out.results,
                       dfg=dataclasses.replace(d, counts=d.counts.at[0, 0]
                                               .add(1)))
        return dataclasses.replace(out, results=results)

    monkeypatch.setattr(engines, "_collect_many", broken)


@pytest.mark.parametrize("fault", [state_unchanged, half_left_out,
                                   answer_altered])
def test_profile_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = rehearse("l1_profile", 2**31 + 7, 2.0, False,
                   **TINY["l1_profile"])
    assert not out["correct"], out["checks"]


def served_answer_altered(monkeypatch):
    from repro.service import server

    real = server.MiningService.collect

    def broken(self, *args, **kwargs):
        out = real(self, *args, **kwargs)
        if out["verb"] == "dfg":
            out["result"]["counts"][0][0] += 1
        return out

    monkeypatch.setattr(server.MiningService, "collect", broken)


def stale_answer(monkeypatch):
    """Each request kind answers with its first payload ever, under the
    snapshot claim of the moment."""
    from repro.service import server

    real = server.MiningService._mine
    first = {}

    def broken(self, fn):
        payload, claim = real(self, fn)
        key = fn.__code__
        first.setdefault(key, payload)
        return first[key], claim

    monkeypatch.setattr(server.MiningService, "_mine", broken)


@pytest.mark.parametrize("fault", [served_answer_altered, stale_answer])
def test_service_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    tiny = dict(TINY["bpic19_dashboard"])
    tiny["mix"] = dict(tiny["mix"], rate_per_s=3.0, sample_per_kind=4)
    out = rehearse("bpic19_dashboard", 2**31 + 8, 6.0, False, **tiny)
    assert not out["correct"], out["checks"]
