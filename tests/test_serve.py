"""Tests for the repro.serve subsystem: percentile math vs numpy,
seeded-loadgen determinism, the SPSC 1P1C contract, per-client FIFO,
mid-flight (barrier-free) admission, admission policies, deadline/SLO
surfacing, config resolution, the scan-prefill contract, and the
benchmarks section registry tripwire."""

import threading
import time

import numpy as np
import pytest

from repro.runtime.config import resolve_serve_config
from repro.serve import (
    STATUS_CANCELLED,
    STATUS_DEADLINE,
    STATUS_ERROR,
    STATUS_OK,
    Gauge,
    Ingest,
    Request,
    Response,
    ServeMetrics,
    ServeScheduler,
    ServeUsageError,
    nearest_rank,
    percentiles,
    poisson_arrivals,
    run_closed_loop,
    run_open_loop,
)


# ---------------------------------------------------------------------------
# percentile math: nearest-rank pinned against numpy's inverted_cdf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("values", [
    [3.0],                              # n=1: every percentile is the sample
    [5.0, 1.0],                         # n=2: rank boundary at q=50
    [2.0, 2.0, 2.0, 2.0],               # all-equal
    [1.0, 1.0, 2.0, 2.0, 3.0],          # ties straddling ranks
    list(range(100)),                   # exact rank arithmetic at p50/p95/p99
    [0.1, 0.2, 0.2, 0.2, 0.9, 0.9, 7.0],
])
@pytest.mark.parametrize("q", [1, 25, 50, 90, 95, 99, 100])
def test_nearest_rank_matches_numpy_inverted_cdf(values, q):
    expected = np.percentile(np.asarray(values), q, method="inverted_cdf")
    assert nearest_rank(sorted(values), q) == pytest.approx(float(expected))


def test_nearest_rank_random_sample_matches_numpy():
    rng = np.random.default_rng(7)
    values = rng.exponential(size=237).tolist()
    ordered = sorted(values)
    for q in (50, 95, 99):
        expected = np.percentile(np.asarray(values), q,
                                 method="inverted_cdf")
        assert nearest_rank(ordered, q) == pytest.approx(float(expected))


def test_nearest_rank_edges():
    assert nearest_rank([4.0, 8.0], 0) == 4.0      # q=0 -> min
    assert nearest_rank([4.0, 8.0], 100) == 8.0
    with pytest.raises(ValueError):
        nearest_rank([], 50)
    with pytest.raises(ValueError):
        nearest_rank([1.0], 101)
    with pytest.raises(ValueError):
        nearest_rank([1.0], -1)


def test_percentiles_returns_observed_samples():
    p = percentiles([0.5, 0.1, 0.9], qs=(50, 95, 99))
    for v in p.values():
        assert v in (0.1, 0.5, 0.9)     # nearest-rank: always a real sample


# ---------------------------------------------------------------------------
# loadgen determinism
# ---------------------------------------------------------------------------

def test_poisson_arrivals_deterministic_per_seed():
    a = poisson_arrivals(100.0, 64, seed=42)
    b = poisson_arrivals(100.0, 64, seed=42)
    c = poisson_arrivals(100.0, 64, seed=43)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.shape == (64,)
    assert np.all(np.diff(a) >= 0)       # cumulative offsets are monotone


def test_poisson_arrivals_rate_scaling_and_validation():
    fast = poisson_arrivals(1000.0, 500, seed=0)
    slow = poisson_arrivals(10.0, 500, seed=0)
    # Same seed => same exponential draws, scaled by 1/rate.
    np.testing.assert_allclose(fast * 100.0, slow, rtol=1e-12)
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 5)
    with pytest.raises(ValueError):
        poisson_arrivals(10.0, -1)


# ---------------------------------------------------------------------------
# Response future semantics
# ---------------------------------------------------------------------------

def _mk_response():
    req = Request(rid=Request.next_rid(), client_id="t", fn=lambda: None,
                  arrival_t=0.0)
    return Response(req)


def test_response_publication_and_result():
    resp = _mk_response()
    assert not resp.done()
    resp._finish(STATUS_OK, value=41, complete_t=1.0)
    assert resp.done() and resp.wait(0) and resp.result() == 41
    assert resp.latency == 1.0


def test_response_error_and_timeout():
    resp = _mk_response()
    assert not resp.wait(timeout=0.01)
    with pytest.raises(TimeoutError):
        resp.result(timeout=0.01)
    resp._finish(STATUS_ERROR, error=ValueError("boom"), complete_t=1.0)
    with pytest.raises(ValueError, match="boom"):
        resp.result()


def test_response_non_ok_statuses_raise_runtimeerror():
    for status in (STATUS_DEADLINE, STATUS_CANCELLED):
        resp = _mk_response()
        resp._finish(status, complete_t=1.0)
        with pytest.raises(RuntimeError):
            resp.result()


def test_response_cross_thread_wait():
    resp = _mk_response()

    def finisher():
        time.sleep(0.02)
        resp._finish(STATUS_OK, value="x", complete_t=2.0)

    t = threading.Thread(target=finisher)
    t.start()
    assert resp.result(timeout=5.0) == "x"
    t.join()


# ---------------------------------------------------------------------------
# ingest: the 1P1C contract, admission policies
# ---------------------------------------------------------------------------

def test_client_handle_is_single_producer():
    ingest = Ingest(resolve_serve_config(queue_depth=4))
    handle = ingest.open_client("c0")
    handle.submit(lambda: 1)             # pins this thread as the producer
    err = []

    def other_thread():
        try:
            handle.submit(lambda: 2)
        except ServeUsageError as e:
            err.append(e)

    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    assert len(err) == 1 and "single-producer" in str(err[0])


def test_reject_admission_counts_overflow():
    cfg = resolve_serve_config(admission="reject", queue_depth=2)
    ingest = Ingest(cfg)
    handle = ingest.open_client("c0")
    accepted = [handle.submit(lambda: None) for _ in range(5)]
    admitted = [r for r in accepted if r is not None]
    assert len(admitted) == 2            # ring depth
    assert handle.rejected == 3
    assert ingest.total_rejected() == 3


def test_block_admission_waits_for_consumer():
    cfg = resolve_serve_config(admission="block", queue_depth=1)
    ingest = Ingest(cfg)
    handle = ingest.open_client("c0")
    filled = threading.Event()
    done = threading.Event()

    def producer():                      # one thread does ALL submits (1P)
        handle.submit(lambda: None)      # fills the ring
        filled.set()
        handle.submit(lambda: None)      # blocks until the consumer drains
        done.set()

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    assert filled.wait(5.0)
    assert not done.wait(0.05)           # still blocked: ring is full
    drained = ingest.poll(8)
    assert len(drained) == 1
    assert done.wait(5.0)                # unblocked by the free slot
    t.join()


def test_duplicate_client_id_rejected():
    ingest = Ingest(resolve_serve_config())
    ingest.open_client("dup")
    with pytest.raises(ServeUsageError):
        ingest.open_client("dup")


def test_ingest_poll_round_robin_fairness():
    ingest = Ingest(resolve_serve_config(queue_depth=8))
    a = ingest.open_client("a")
    b = ingest.open_client("b")
    for i in range(4):
        a.submit(lambda: None)
        b.submit(lambda: None)
    batch = ingest.poll(4)
    clients = {r.request.client_id for r in batch}
    assert clients == {"a", "b"}         # a hot client cannot starve others


# ---------------------------------------------------------------------------
# scheduler: FIFO, mid-flight admission, deadlines, errors, drain
# ---------------------------------------------------------------------------

def test_per_client_fifo_execution_order():
    order = []
    with ServeScheduler(lanes=1) as server:
        client = server.open_client("c0")
        resps = [client.submit(order.append, i) for i in range(16)]
        for r in resps:
            assert r.wait(30.0)
    assert order == list(range(16))      # per-client FIFO through the lane


def test_mid_flight_admission_no_batch_barrier():
    """A request admitted while another is in flight must complete without
    waiting for any batch barrier — the continuous-batching pin."""
    gate = threading.Event()
    running = threading.Event()

    def blocker():
        running.set()
        assert gate.wait(30.0)
        return "blocked-done"

    with ServeScheduler(lanes=2) as server:
        client = server.open_client("c0")
        resp_a = client.submit(blocker)
        assert running.wait(30.0)        # A is mid-flight on a lane
        resp_b = client.submit(lambda: "quick")
        # B finishes while A is still blocked: no barrier between them.
        assert resp_b.wait(30.0), "mid-flight admission blocked on a barrier"
        assert resp_b.result() == "quick"
        assert not resp_a.done()
        gate.set()
        assert resp_a.result(30.0) == "blocked-done"


def test_deadline_exceeded_is_surfaced_not_silent():
    with ServeScheduler(lanes=1) as server:
        client = server.open_client("c0")
        # Already-expired deadline: shed at admission, surfaced as a
        # deadline_exceeded response (never run, never silently dropped).
        resp = client.submit(lambda: "never", deadline_s=-0.001)
        assert resp.wait(30.0)
        assert resp.status == STATUS_DEADLINE
        with pytest.raises(RuntimeError, match="deadline_exceeded"):
            resp.result()
    # Metrics are folded in by the loop; read them after stop() has joined.
    assert server.stats()["deadline_exceeded"] == 1


def test_deadline_exceeded_after_running_long_task():
    with ServeScheduler(lanes=1) as server:
        client = server.open_client("c0")
        resp = client.submit(lambda: time.sleep(0.05), deadline_s=0.01)
        assert resp.wait(30.0)
    assert resp.status == STATUS_DEADLINE


def test_task_error_contained_and_serving_continues():
    def boom():
        raise KeyError("bad request")

    with ServeScheduler(lanes=2) as server:
        client = server.open_client("c0")
        bad = client.submit(boom)
        good = client.submit(lambda: 7)
        assert good.result(30.0) == 7    # the error did not poison the lane
        assert bad.wait(30.0)
        assert bad.status == STATUS_ERROR
        assert isinstance(bad.error, KeyError)
    # Metrics are folded in by the loop; read them after stop() has joined.
    stats = server.stats()
    assert stats["errors"] == 1 and stats["ok"] == 1


def test_streaming_request_stamps_first_result():
    def stream():
        yield 1
        time.sleep(0.01)
        yield 2

    with ServeScheduler(lanes=1) as server:
        client = server.open_client("c0")
        resp = client.submit(stream)
        assert resp.result(30.0) == [1, 2]
    assert resp.first_result_t is not None
    assert resp.complete_t is not None
    assert resp.first_result_t < resp.complete_t


def test_stop_drains_queued_requests():
    server = ServeScheduler(lanes=1).start()
    client = server.open_client("c0")
    resps = [client.submit(lambda i=i: i * i) for i in range(8)]
    server.stop(drain=True)
    assert [r.result() for r in resps] == [i * i for i in range(8)]


def test_lanes_zero_inline_mode():
    with ServeScheduler(lanes=0) as server:
        client = server.open_client("c0")
        assert client.submit(lambda: "inline").result(30.0) == "inline"


def test_closed_and_open_loop_end_to_end():
    with ServeScheduler(lanes=2) as server:
        res = run_closed_loop(server, lambda: ((lambda: 5), ()),
                              clients=2, requests_per_client=4)
    assert res.offered == 8 and len(res.responses) == 8
    assert all(r.result() == 5 for r in res.responses)

    cfg = resolve_serve_config(admission="reject")
    with ServeScheduler(lanes=2, config=cfg) as server:
        res = run_open_loop(server, lambda: ((lambda: 6), ()),
                            rate_rps=2000.0, n_requests=16, seed=3)
    assert res.offered == 16
    assert res.offered == len(res.responses) + res.rejected
    assert all(r.result() == 6 for r in res.responses)


# ---------------------------------------------------------------------------
# metrics accounting
# ---------------------------------------------------------------------------

def test_gauge_tracks_last_min_max_mean():
    g = Gauge()
    for v in (4.0, 1.0, 7.0):
        g.observe(v)
    assert (g.last, g.min, g.max, g.mean) == (7.0, 1.0, 7.0, 4.0)
    assert Gauge().asdict() == {"last": 0.0, "min": 0.0, "max": 0.0,
                                "mean": 0.0}


def test_serve_metrics_snapshot_counts_statuses():
    m = ServeMetrics()
    for i, (status, t) in enumerate([(STATUS_OK, 1.0), (STATUS_ERROR, 2.0),
                                     (STATUS_DEADLINE, 3.0)]):
        req = Request(rid=i, client_id="c", fn=lambda: None, arrival_t=0.5)
        req.admit_t = 0.75
        resp = Response(req)
        resp._finish(status, complete_t=t)
        m.note_complete(resp)
    snap = m.snapshot(rejected=2)
    assert snap["completed"] == 3 and snap["ok"] == 1
    assert snap["errors"] == 1 and snap["deadline_exceeded"] == 1
    assert snap["rejected"] == 2
    assert snap["latency_s"]["n"] == 3
    assert snap["latency_s"]["p50"] == 1.5            # 2.0 - 0.5
    # throughput over the observed 0.5s..3.0s span
    assert snap["throughput_rps"] == pytest.approx(3 / 2.5)


# ---------------------------------------------------------------------------
# config resolution (RELIC_SERVE_*)
# ---------------------------------------------------------------------------

def test_serve_config_defaults():
    cfg = resolve_serve_config()
    assert cfg.admission == "block" and cfg.queue_depth == 64
    assert cfg.batch_max == 8 and cfg.deadline_ms is None


def test_serve_config_reads_env_per_instance(monkeypatch):
    monkeypatch.setenv("RELIC_SERVE_ADMISSION", "reject")
    monkeypatch.setenv("RELIC_SERVE_QUEUE_DEPTH", "16")
    monkeypatch.setenv("RELIC_SERVE_BATCH_MAX", "3")
    monkeypatch.setenv("RELIC_SERVE_DEADLINE_MS", "12.5")
    cfg = resolve_serve_config()
    assert cfg.admission == "reject" and cfg.queue_depth == 16
    assert cfg.batch_max == 3 and cfg.deadline_ms == 12.5
    # Re-read per instance, not frozen at import.
    monkeypatch.setenv("RELIC_SERVE_QUEUE_DEPTH", "32")
    assert resolve_serve_config().queue_depth == 32


def test_serve_config_kwargs_override_env(monkeypatch):
    monkeypatch.setenv("RELIC_SERVE_ADMISSION", "reject")
    assert resolve_serve_config(admission="block").admission == "block"


@pytest.mark.parametrize("var,bad", [
    ("RELIC_SERVE_ADMISSION", "maybe"),
    ("RELIC_SERVE_QUEUE_DEPTH", "0"),
    ("RELIC_SERVE_QUEUE_DEPTH", "many"),
    ("RELIC_SERVE_BATCH_MAX", "-2"),
    ("RELIC_SERVE_DEADLINE_MS", "soon"),
    ("RELIC_SERVE_DEADLINE_MS", "-5"),
])
def test_serve_config_invalid_env_raises(monkeypatch, var, bad):
    monkeypatch.setenv(var, bad)
    with pytest.raises(ValueError):
        resolve_serve_config()


def test_spin_pause_every_still_importable_from_relic():
    # Back-compat: the knob moved to repro.runtime.config but relic is
    # where existing callers import it from.
    from repro.core.relic import resolve_spin_pause_every as via_relic
    from repro.runtime.config import resolve_spin_pause_every as via_config
    assert via_relic is via_config


# ---------------------------------------------------------------------------
# scan-prefill contract (launch satellite)
# ---------------------------------------------------------------------------

def test_prefill_scan_matches_per_token_decode_loop():
    """make_prefill_step (one lax.scan dispatch) must produce the same
    next-token prediction AND a functionally identical cache as feeding
    the prompt one token at a time through serve_step — the cache-position
    contract (pos advances by exactly 1 per single-token decode_step)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.steps import make_prefill_step, make_serve_step
    from repro.models import build_model

    cfg = get_config("relic_tiny", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch, plen, gen = 2, 5, 3
    cache_len = plen + gen
    prompts = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (batch, plen)),
        jnp.int32)
    serve_step = jax.jit(make_serve_step(model))
    prefill = jax.jit(make_prefill_step(model))

    # Reference: the one-token-at-a-time teacher-forced loop.
    cache_ref = model.init_cache(batch, cache_len)
    tok_ref = logits_ref = None
    for t in range(plen):
        tok_ref, logits_ref, cache_ref = serve_step(
            params, cache_ref, prompts[:, t:t + 1], jnp.int32(t))

    # Scan prefill: one dispatch.
    cache_scan = model.init_cache(batch, cache_len)
    tok_scan, logits_scan, cache_scan = prefill(params, cache_scan, prompts)

    np.testing.assert_array_equal(np.asarray(tok_ref), np.asarray(tok_scan))
    np.testing.assert_allclose(np.asarray(logits_ref), np.asarray(logits_scan),
                               rtol=1e-6, atol=1e-6)
    # The caches must be functionally identical: decoding from both must
    # yield the same tokens at every subsequent step.
    for t in range(plen, plen + gen):
        tok_ref, _, cache_ref = serve_step(
            params, cache_ref, tok_ref, jnp.int32(t))
        tok_scan, _, cache_scan = serve_step(
            params, cache_scan, tok_scan, jnp.int32(t))
        np.testing.assert_array_equal(
            np.asarray(tok_ref), np.asarray(tok_scan))


# ---------------------------------------------------------------------------
# benchmarks section registry (satellite tripwire)
# ---------------------------------------------------------------------------

def test_benchmark_registry_matches_run_functions():
    """Every top-level run_* function in benchmarks.run is reachable from
    the CLI section registry (or is a documented helper a section calls),
    and every registry value is one of those functions — a new section
    cannot be added without wiring it into --only/--list-sections."""
    import benchmarks.run as br

    helpers = {"run_spsc_overhead"}      # called by run_spsc, not a section
    run_fns = {name for name in vars(br)
               if name.startswith("run_") and callable(getattr(br, name))}
    registered = {fn.__name__ for fn in br.SECTION_RUNNERS.values()}
    assert registered <= run_fns
    assert run_fns - helpers == registered
    assert list(br.SECTION_RUNNERS) == br.SECTIONS
    assert "serve" in br.SECTION_RUNNERS


def test_benchmark_cli_rejects_unknown_section(capsys):
    import benchmarks.run as br

    with pytest.raises(SystemExit) as exc:
        br.main(["--only", "sacling"])
    assert "sacling" in str(exc.value)


def test_benchmark_cli_list_sections(capsys):
    import benchmarks.run as br

    with pytest.raises(SystemExit) as exc:
        br.main(["--list-sections"])
    assert exc.value.code == 0
    out = capsys.readouterr().out.split()
    assert out == br.SECTIONS
