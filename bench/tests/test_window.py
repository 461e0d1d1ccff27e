"""Window arithmetic: rates to the last answer, percentiles, the job in
flight at the close."""
import statistics
import time
import types

import pytest

from bench import harness, spans, stats, traffic
from bench.tests.conftest import ROOT


def test_rate_runs_to_the_last_answer():
    assert stats.rate(10, 100.0, 104.0) == 2.5
    with pytest.raises(ValueError):
        stats.rate(0, 1.0, 2.0)


def test_percentiles_match_statistics_and_need_two_values():
    v = [float(x) for x in range(1, 41)]
    assert stats.percentile(v, 50) == statistics.median(v)
    assert stats.percentile(v, 95) == statistics.quantiles(v, n=100)[94]
    with pytest.raises(ValueError):
        stats.percentile([1.0], 50)


def test_spread_summary_is_interquartile_over_median():
    from bench import spread

    recs = [{"result": {"metrics": {"m": {"value": float(v)}}}}
            for v in (1, 2, 3, 4, 5, 6)]
    q1, med, q3 = statistics.quantiles([1, 2, 3, 4, 5, 6], n=4)
    assert spread.summary(recs)["m"] == {"n": 6, "median": med,
                                         "spread": (q3 - q1) / med}


class FakeScheduler:
    """Answers everything queued, taking ``service`` seconds a tick."""

    def __init__(self, service: float):
        self.service, self.queue, self.qid = service, [], 0

    def submit(self, graph, source, target=None):
        q = types.SimpleNamespace(qid=self.qid, source=source, target=target)
        self.qid += 1
        self.queue.append(q)
        return q

    def tick(self):
        time.sleep(self.service)
        out = [types.SimpleNamespace(query=q, value=0.0, via="target",
                                     ok=True, exact=True) for q in self.queue]
        self.queue = []
        return out


def fake_jax():
    import contextlib

    return types.SimpleNamespace(profiler=types.SimpleNamespace(
        TraceAnnotation=lambda name: contextlib.nullcontext()))


def test_closed_loop_finishes_and_counts_the_job_in_flight():
    jobs = [traffic.Job(((i, i + 1),), label="k") for i in range(100)]
    w = harness.drive(fake_jax(), FakeScheduler(0.03), ["g"], jobs,
                      seconds=0.1)
    # the last job went out before the close and came back after it
    assert w.sent[-1].sent < w.start + 0.1 < w.sent[-1].done
    assert all(s.done > s.sent for s in w.sent) and w.passes == 1
    assert len(w.late_ms) == len(w.sent)
    ctx = {"window": w}
    qps = harness.reader("p2p_qps", ROOT)(ctx)
    assert qps == pytest.approx(len(w.sent) / (w.last - w.start))
    lat = [s.done - s.sent for s in w.sent]
    assert harness.reader("p2p_ms_p50", ROOT)(ctx) == pytest.approx(
        stats.percentile(lat, 50) * 1e3)
    assert len(spans.queries(w, "p2p")) == len(w.sent)
    assert spans.queries(w, "rows") == []


def test_the_job_list_starts_again_when_it_runs_out():
    jobs = [traffic.Job(((i, None), (i + 5, None))) for i in range(3)]
    w = harness.drive(fake_jax(), FakeScheduler(0.005), ["g"], jobs,
                      seconds=0.1)
    assert len(w.sent) > 3 and w.passes == -(-len(w.sent) // 3)
    assert [s.job for s in w.sent[:6]] == jobs + jobs
    assert all(len(s.answers) == 2 for s in w.sent)
    rows = harness.reader("rows_per_s", ROOT)({"window": w})
    assert rows == pytest.approx(2 * len(w.sent) / (w.last - w.start))


def test_a_warm_up_sends_its_one_job():
    jobs = [traffic.Job(((1, 2),), label="warm")]
    w = harness.drive(fake_jax(), FakeScheduler(0.0), ["g"], jobs, seconds=0.0)
    assert len(w.sent) == 1 and len(w.sent[0].answers) == 1
