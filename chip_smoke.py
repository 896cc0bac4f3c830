"""Chip smoke run: the three parts of the main path, once each, on a TPU.

One chip (the default) runs three phases through the entry points a user
calls, with random weights from seed 0:

  serve     phi3-mini-3.8B at its full published width (bf16 params) answers
            3 requests of 128 prompt + 32 generated tokens through
            ``repro.launch.serve`` and ``ServeScheduler``. Check: the logit
            served with each token agrees with the teacher-forced
            ``lm_forward``'s logit of that token over prompt + generated
            tokens, at the last prompt position and at every generated
            position.
  train     relic_tiny at its full config takes 20 steps through
            ``repro.launch.train.main`` (Relic-prefetched data, Relic
            checkpoint writer, a save every 10 steps), then resumes to step
            30. Check: every loss finite, the loss falls from its first
            logged value, the second run resumes at step 20 and takes the
            10 steps left.
  hosttask  every ``repro.workloads`` workload runs serial, then paired and
            chunked under ``TaskScope("relic")``, whose assistant thread
            issues the device work. Check: each workload's oracle.

``--chips 4`` (one host, a 2x2 mesh) runs only what exists across chips:

  sharded   one relic_tiny train step on a (2, 2) ("data", "model") mesh,
            built as ``launch/train.py`` builds it, against the same step
            on one device. Check: loss and gradient norm agree.
  ring      ``tp_allgather_matmul`` and ``tp_matmul_reducescatter`` on a
            (4,) "model" mesh at phi3-mini MLP widths against their
            ``overlapped=False`` forms. Check: the results agree.

Both check that their arrays span all 4 devices. Each phase prints one JSON
line (sizes, device memory, wall seconds including compilation, check); the
last line is ``{"ok": true, "device": {...}}``. A failed check raises, so the
script exits non-zero; so does a run where JAX finds no TPU.

Usage:
  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # one host of four chips
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import io
import json
import re
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import sharding as shd  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.collective_matmul import (  # noqa: E402
    tp_allgather_matmul, tp_matmul_reducescatter)
from repro.launch import serve as serve_launch  # noqa: E402
from repro.launch import train as train_launch  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.steps import make_train_state, make_train_step  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.lm import lm_forward  # noqa: E402
from repro.optim import OptConfig  # noqa: E402
from repro.runtime.config import enable_compile_cache  # noqa: E402
from repro.tasks.api import TaskScope  # noqa: E402
from repro.workloads import available_workloads, make_workload  # noqa: E402

# Serve sizes on one v5e chip (16 GiB HBM): the largest batch and cache whose
# prefill and decode programs compile for a described v5e with at least
# 1 GiB to spare (tests/test_tpu_compile.py holds them to that).
SERVE_BATCH = 4
SERVE_CACHE_LEN = 1024
PROMPT_LEN = 128
GEN_LEN = 32
N_REQUESTS = 3

# Cached-path vs teacher-forced logits, elementwise as np.allclose: bf16
# params, activations and KV cache keep 8 significant bits (~0.4% per
# rounding), and the two paths round in different orders (single-token
# attention over a masked cache against full causal attention), so equal
# math differs by bf16 noise that grows with depth. The logits have std ~1
# (unit-rms final norm, d**-0.5 head init), so this bound is ~15% of the
# logit spread; it is tests/test_models.py's decode-consistency bound.
LOGIT_RTOL = LOGIT_ATOL = 0.15
# Sharded vs one-device train step: the same f32-master step with bf16
# compute, partitioned differently, so only reduction order differs.
LOSS_RTOL = 1e-2
GNORM_RTOL = 5e-2
# Ring vs unoverlapped collective matmul, as max|ring - ref| / max|ref|: bf16
# operands and outputs; the reference reduce-scatters bf16 partial sums
# while the ring accumulates in f32, ~4 bf16 roundings (2**-8 each) apart.
RING_RTOL = 2e-2


def _memory() -> dict:
    """Device memory as the backend reports it (nothing on the CPU)."""
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out[str(d.id)] = {k: stats[k] for k in
                          ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                          if k in stats}
    return out


def _report(phase: str, t0: float, ok: bool, **fields) -> None:
    line = {"phase": phase, "ok": ok,
            "wall_s": time.perf_counter() - t0, **fields,
            "memory": _memory()}
    print(json.dumps(line), flush=True)
    if not ok:
        raise AssertionError(f"phase {phase} failed its check: {line}")


def serve_phase(cfg, *, batch: int, prompt_len: int, gen: int,
                cache_len: int, n_requests: int, seed: int = 0) -> None:
    t0 = time.perf_counter()
    model, params = serve_launch.load(cfg)
    rng = np.random.default_rng(seed)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size,
                                        (batch, prompt_len)), jnp.int32)
               for _ in range(n_requests)]
    resps = serve_launch.serve(model, params, prompts, gen=gen,
                               cache_len=cache_len)
    t_served = time.perf_counter() - t0

    forward = jax.jit(functools.partial(lm_forward, model.cfg))
    excess, max_err, ref_std = [], [], []
    for prompt, resp in zip(prompts, resps):
        out = resp.result()
        toks = jnp.concatenate([t for t, _ in out], axis=1)       # [B, gen]
        cached = jnp.concatenate([lg for _, lg in out], axis=1)   # [B, gen]
        ref, _ = forward(params, jnp.concatenate([prompt, toks[:, :-1]], 1))
        # the forward pass's logits of the served tokens
        ref = jnp.take_along_axis(ref[:, prompt_len - 1:], toks[..., None],
                                  -1)[..., 0]
        err = jnp.abs(cached - ref)
        excess.append(float(jnp.max(err - LOGIT_ATOL
                                    - LOGIT_RTOL * jnp.abs(ref))))
        max_err.append(float(jnp.max(err)))
        ref_std.append(float(jnp.std(ref)))
    _report("serve", t0, max(excess) <= 0.0, arch=cfg.name, batch=batch,
            prompt_len=prompt_len, gen=gen, cache_len=cache_len,
            requests=len(resps), served_s=t_served,
            ttft_s=[r.first_result_t - r.request.arrival_t for r in resps],
            logits_max_abs_err=max_err, logits_ref_std=ref_std,
            logits_rtol=LOGIT_RTOL, logits_atol=LOGIT_ATOL)


def _train(argv: list) -> str:
    """Run ``launch/train.py``'s main, returning what it printed."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        train_launch.main(argv)
    sys.stderr.write(buf.getvalue())
    return buf.getvalue()


def train_phase(arch: str, *, batch: int, seq: int, steps: int,
                resume_to: int, ckpt_every: int, smoke: bool = False) -> None:
    t0 = time.perf_counter()
    step_re = re.compile(r"^step\s+(\d+)\s+loss\s+(\S+)", re.M)
    with tempfile.TemporaryDirectory() as ckpt:
        common = ["--arch", arch, "--batch", str(batch), "--seq", str(seq),
                  "--ckpt", ckpt, "--ckpt-every", str(ckpt_every),
                  "--log-every", "1"] + (["--smoke"] if smoke else [])
        first = _train(common + ["--steps", str(steps)])
        second = _train(common + ["--steps", str(resume_to), "--resume"])
    run1 = [(int(s), float(v)) for s, v in step_re.findall(first)]
    run2 = [(int(s), float(v)) for s, v in step_re.findall(second)]
    losses = [v for _, v in run1 + run2]
    ok = (all(np.isfinite(losses))
          and [s for s, _ in run1] == list(range(1, steps + 1))
          and run1[-1][1] < run1[0][1]
          and f"resumed from step {steps}" in second
          and [s for s, _ in run2] == list(range(steps + 1, resume_to + 1)))
    _report("train", t0, ok, arch=arch, batch=batch, seq=seq,
            steps=steps, resumed_to=resume_to, ckpt_every=ckpt_every,
            first_loss=run1[0][1] if run1 else None,
            loss_at_steps=run1[-1][1] if run1 else None,
            final_loss=run2[-1][1] if run2 else None)


def hosttask_phase(substrate: str = "relic") -> None:
    t0 = time.perf_counter()
    seconds = {}
    for name in available_workloads():
        t = time.perf_counter()
        w = make_workload(name)
        w.check(w.serial())
        with TaskScope(substrate) as scope:
            w.check(w.paired(scope))
            w.check(w.chunked(scope, grain=1))
        seconds[name] = time.perf_counter() - t
    _report("hosttask", t0, True, substrate=substrate,
            workloads_s=seconds)


def _rel_err(got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _spans(tree, n: int) -> bool:
    return all(len(x.sharding.device_set) == n for x in jax.tree.leaves(tree))


def sharded_train_phase(cfg, *, batch: int, seq: int, seed: int = 0) -> None:
    t0 = time.perf_counter()
    model = build_model(cfg)
    oc = OptConfig(warmup_steps=1, total_steps=10)
    rng = np.random.default_rng(seed)
    data = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                              jnp.int32),
        "mask": jnp.ones((batch, seq), jnp.float32),
    }
    state = make_train_state(model, jax.random.PRNGKey(0))
    _, m1 = jax.jit(make_train_step(model, oc))(state, data)
    loss1, gnorm1 = float(m1["loss"]), float(m1["grad_norm"])
    del state

    mesh = make_mesh((2, 2), ("data", "model"))
    with shd.use_sharding_rules(mesh):
        state = make_train_state(model, jax.random.PRNGKey(0))
        state_sh = shd.named_shardings(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         state), mesh)
        state = jax.tree.map(jax.device_put, state, state_sh)
        new_state, m2 = jax.jit(make_train_step(model, oc))(state, data)
    loss2, gnorm2 = float(m2["loss"]), float(m2["grad_norm"])
    n = mesh.devices.size
    split = sum(x.addressable_shards[0].data.shape != x.shape
                for x in jax.tree.leaves(new_state["params"]))
    ok = (abs(loss2 - loss1) <= LOSS_RTOL * abs(loss1)
          and abs(gnorm2 - gnorm1) <= GNORM_RTOL * abs(gnorm1)
          and _spans(new_state, n) and split > 0)
    _report("sharded", t0, ok, arch=cfg.name, batch=batch, seq=seq,
            mesh=dict(mesh.shape), devices=n, params_split=split,
            loss_one_device=loss1, loss_sharded=loss2,
            gnorm_one_device=gnorm1, gnorm_sharded=gnorm2,
            loss_rtol=LOSS_RTOL, gnorm_rtol=GNORM_RTOL)


def ring_matmul_phase(*, s: int, d: int, f: int, dtype=jnp.bfloat16,
                      seed: int = 0) -> None:
    t0 = time.perf_counter()
    mesh = make_mesh((len(jax.devices()),), ("model",))
    n = mesh.devices.size
    rng = np.random.default_rng(seed)

    def put(shape, scale, spec):
        x = jnp.asarray(rng.normal(size=shape) * scale, dtype)
        return jax.device_put(x, NamedSharding(mesh, spec))

    x = put((s, d), 1.0, P("model", None))           # sequence-sharded
    w1 = put((d, f), d ** -0.5, P(None, "model"))    # column-parallel
    w2 = put((f, d), f ** -0.5, P("model", None))    # row-parallel
    ag = functools.partial(tp_allgather_matmul, mesh=mesh)
    rs = functools.partial(tp_matmul_reducescatter, mesh=mesh)
    y = jax.jit(ag)(x, w1)
    y_ref = jax.jit(functools.partial(ag, overlapped=False))(x, w1)
    z = jax.jit(rs)(y, w2)
    z_ref = jax.jit(functools.partial(rs, overlapped=False))(y, w2)
    errs = {"allgather_matmul": _rel_err(y, y_ref),
            "matmul_reducescatter": _rel_err(z, z_ref)}
    ok = (max(errs.values()) <= RING_RTOL
          and _spans([x, w1, w2, y, y_ref, z, z_ref], n))
    _report("ring", t0, ok, s=s, d=d, f=f, dtype=jnp.dtype(dtype).name,
            mesh=dict(mesh.shape), devices=n, rel_err=errs,
            rel_tol=RING_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the main path once on a TPU and check it.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip phases, on a 2x2 host")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: --chips 4 needs 4 devices; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    if args.chips == 4:
        sharded_train_phase(get_config("relic_tiny"), batch=8, seq=256)
        ring_matmul_phase(s=2048, d=3072, f=8192)
    else:
        serve_phase(get_config("phi3_mini_3p8b"), batch=SERVE_BATCH,
                    prompt_len=PROMPT_LEN, gen=GEN_LEN,
                    cache_len=SERVE_CACHE_LEN, n_requests=N_REQUESTS)
        train_phase("relic_tiny", batch=8, seq=256, steps=20, resume_to=30,
                    ckpt_every=10)
        hosttask_phase()

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
