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


def test_submit_group_gives_each_member_its_parts():
    def stream():
        yield ["a0", "b0", "c0"]
        time.sleep(0.01)
        yield ["a1", "b1", "c1"]

    with ServeScheduler(lanes=1) as server:
        client = server.open_client("c0")
        resps = client.submit_group(stream, 3)
        assert [r.result(30.0) for r in resps] == [
            ["a0", "a1"], ["b0", "b1"], ["c0", "c1"]]
        # scalar work: one value per member
        assert [r.result(30.0) for r in client.submit_group(
            lambda x: (x, x + 1), 2, 5)] == [5, 6]
    lead = resps[0].request
    assert len({r.request.rid for r in resps}) == 3
    for r in resps:
        assert r.status == STATUS_OK
        assert r.request.arrival_t == lead.arrival_t
        assert r.request.admit_t == lead.admit_t is not None
        assert r.request.admit_t <= r.first_result_t < r.complete_t
    stats = server.stats()
    assert stats["completed"] == stats["ok"] == stats["admitted"] == 5


def test_failing_group_fails_every_member():
    def boom():
        yield [1, 2, 3]
        raise KeyError("bad group")

    with ServeScheduler(lanes=1) as server:
        client = server.open_client("c0")
        resps = client.submit_group(boom, 3)
        short = client.submit_group(lambda: [1], 2)     # one part for two
        for r in resps + short:
            assert r.wait(30.0)
    assert all(r.status == STATUS_ERROR and isinstance(r.error, KeyError)
               for r in resps)
    assert all(isinstance(r.error, ValueError) for r in short)
    assert len({r.complete_t for r in resps}) == 1
    assert server.stats()["errors"] == 5
    with pytest.raises(ValueError):
        ServeScheduler(lanes=0).open_client("c1").submit_group(lambda: [], 0)


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


@pytest.mark.parametrize("arch", ["relic_tiny", "arctic_480b", "rwkv6_1p6b",
                                  "zamba2_1p2b", "deepseek_v3"],
                         ids=["dense", "moe", "ssm", "hybrid", "mla_moe"])
def test_serve_programs_match_plain_jits(arch):
    """Prefill then decode through serve()'s programs (the cache made by
    their jitted init, held in its layout through the layer loops, donated)
    yields the same items, tokens and their logits, as plain jits of the
    same steps; each item's logits are the forward pass's logits of the
    served tokens."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.serve import cache_programs
    from repro.launch.steps import make_prefill_step, make_serve_step
    from repro.models import build_model
    from repro.models.lm import lm_forward

    cfg = get_config(arch, smoke=True)
    if cfg.mla is not None:
        # In bfloat16 a near-tie of the router flips an expert between the
        # forward pass and decode at this width; float32 keeps the forward
        # comparison tight.
        cfg = cfg.replace(compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch, plen, gen = 2, 4, 4
    cache_len = plen + gen
    prompts = jnp.asarray(np.random.default_rng(2).integers(
        0, model.cfg.vocab_size, (batch, plen)), jnp.int32)

    def run(new_cache, prefill, step):
        tok, logits, cache = prefill(params, new_cache(), prompts)
        out = [(tok, logits)]
        for t in range(plen, plen + gen - 1):
            tok, logits, cache = step(params, cache, tok, jnp.int32(t))
            out.append((tok, logits))
        return out

    new_cache, prefill, step = cache_programs(model, params, batch, cache_len)
    served = run(lambda: new_cache(params, None), prefill, step)
    plain = run(lambda: model.init_cache(batch, cache_len),
                jax.jit(make_prefill_step(model)),
                jax.jit(make_serve_step(model)))
    for (tok_s, logits_s), (tok_p, logits_p) in zip(served, plain):
        assert tok_s.shape == logits_s.shape == (batch, 1)
        np.testing.assert_array_equal(np.asarray(tok_s), np.asarray(tok_p))
        np.testing.assert_array_equal(np.asarray(logits_s),
                                      np.asarray(logits_p))
    toks = jnp.concatenate([t for t, _ in served], 1)             # [B, gen]
    ref, _ = lm_forward(cfg, params, jnp.concatenate([prompts, toks[:, :-1]],
                                                     1))
    ref = jnp.take_along_axis(ref[:, plen - 1:], toks[..., None], -1)[..., 0]
    # bf16 activations: decode and the forward pass round differently
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([lg for _, lg in served], 1)),
        np.asarray(ref), rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# request groups: equal-shape queued requests served as one batch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config
    from repro.launch.serve import load

    return load(get_config("relic_tiny", smoke=True))


def _prompts(model, shapes, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, model.cfg.vocab_size, shape),
                        jnp.int32) for shape in shapes]


def _served(resp):
    out = resp.result()
    return (np.concatenate([np.asarray(tok) for tok, _ in out], 1),
            np.concatenate([np.asarray(lg) for _, lg in out], 1))


def _groups(resps):
    """The queue's groups, as the members' shared completion times show
    them: lists of indices, in order."""
    groups = []
    for i, r in enumerate(resps):
        if groups and resps[groups[-1][0]].complete_t == r.complete_t:
            groups[-1].append(i)
        else:
            groups.append([i])
    return groups


@pytest.mark.parametrize("keys,rows,max_rows,fits,want", [
    ("aaaa", [2, 2, 2, 2], 8, None, [[0, 1, 2, 3]]),
    ("aaba", [2, 2, 2, 2], 8, None, [[0, 1], [2], [3]]),
    ("aaaaa", [2, 2, 2, 2, 2], 4, None, [[0, 1], [2, 3], [4]]),
    ("aaaa", [2, 6, 2, 2], 4, None, [[0], [1], [2, 3]]),
    ("aaaa", [2, 2, 2, 2], None, None, [[0], [1], [2], [3]]),
    ("aaaa", [2, 2, 2, 2], 8, 4, [[0, 1], [2, 3]]),
], ids=["equal", "shapes", "row_cap", "over_cap_alone", "no_cap", "memory"])
def test_plan_groups(keys, rows, max_rows, fits, want):
    from repro.launch.serve import plan_groups

    fit = (lambda r: True) if fits is None else (lambda r: r <= fits)
    assert plan_groups(list(keys), rows, max_rows, fit) == want


def test_equal_shape_requests_serve_as_one_group(tiny):
    from repro.launch.serve import serve

    model, params = tiny
    prompts = _prompts(model, [(2, 5)] * 4)
    merged = serve(model, params, prompts, gen=4, cache_len=12, max_rows=8)
    assert _groups(merged) == [[0, 1, 2, 3]]
    assert len({r.request.admit_t for r in merged}) == 1
    for prompt, resp in zip(prompts, merged):
        (alone,) = serve(model, params, [prompt], gen=4, cache_len=12)
        toks, logits = _served(resp)
        toks_alone, logits_alone = _served(alone)
        assert toks.shape == (2, 4)
        np.testing.assert_array_equal(toks, toks_alone)
        np.testing.assert_allclose(logits, logits_alone, rtol=0.05,
                                   atol=0.05)


def test_unequal_shapes_never_merge(tiny):
    from repro.launch.serve import serve

    model, params = tiny
    prompts = _prompts(model, [(2, 5), (2, 6), (1, 5), (2, 5)])
    resps = serve(model, params, prompts, gen=3, cache_len=12, max_rows=64)
    assert _groups(resps) == [[0], [1], [2], [3]]
    assert [_served(r)[0].shape for r in resps] == [(2, 3), (2, 3), (1, 3),
                                                    (2, 3)]
    # each decodes from its own prompt length
    (alone,) = serve(model, params, prompts[1:2], gen=3, cache_len=12)
    np.testing.assert_array_equal(_served(resps[1])[0], _served(alone)[0])


def test_row_cap_splits_groups_in_queue_order(tiny):
    from repro.launch.serve import serve

    model, params = tiny
    prompts = _prompts(model, [(2, 5), (2, 5), (2, 5), (5, 5), (2, 5)])
    resps = serve(model, params, prompts, gen=3, cache_len=8, max_rows=4)
    assert _groups(resps) == [[0, 1], [2], [3], [4]]
    assert [r.status for r in resps] == [STATUS_OK] * 5


def test_nothing_merges_on_a_chip_not_in_the_table(tiny):
    from repro.launch.peaks import ridge_rows
    from repro.launch.serve import serve

    model, params = tiny
    assert ridge_rows("cpu") is None
    assert ridge_rows("TPU v5 lite") == 240
    resps = serve(model, params, _prompts(model, [(2, 5)] * 3), gen=3,
                  cache_len=8)
    assert _groups(resps) == [[0], [1], [2]]
    assert resps[0].complete_t <= resps[1].first_result_t


def test_queue_is_iterated_once_after_its_programs_compile(tiny):
    """serve() reads the queue by len() and indexing, compiles every
    group's programs, then iterates it once: nothing compiles after."""
    from jax import monitoring
    from jax._src.monitoring import unregister_event_duration_listener

    from repro.launch.serve import serve

    model, params = tiny
    compiles, at_iter = [], []

    def on_duration(event, seconds, **_):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    class Queue(list):
        def __iter__(self):
            at_iter.append(len(compiles))
            return super().__iter__()

    queue = Queue(_prompts(model, [(2, 7)] * 3 + [(1, 7)], seed=3))
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        resps = serve(model, params, queue, gen=3, cache_len=10, max_rows=4)
    finally:
        unregister_event_duration_listener(on_duration)
    assert _groups(resps) == [[0, 1], [2], [3]]
    assert len(at_iter) == 1
    assert at_iter[0] > 0 and len(compiles) == at_iter[0]


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
