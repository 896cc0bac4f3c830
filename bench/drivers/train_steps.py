"""Back-to-back training steps of a dense LM, fed by Relic prefetch.

The program under test is the training path of ``repro.launch.train``:
``make_train_step`` (forward, loss, backward, global-norm clipping, AdamW)
jitted with the state donated, on the ``make_host_mesh`` mesh under its
sharding rules, fed by ``PrefetchPipeline`` (the Relic assistant makes and
queues batches). The loop is ``launch/train.py``'s: take a batch, step,
and every ``log_every`` steps read the loss back, which is the only sync.
``train.main`` itself is not called: it takes no seed, no time limit and
no configuration from a file, and hands back no state to check.

Set-up makes the weights and the batches from the seed, builds the state
and the compiled step once, and drives that same state through the first
``checked_steps`` steps with the window's own call and feed; the window
then continues from there. Tokens per second are taken between the first
and the last sync of the window.

Check, against the plain float32 reference (``bench/reference/lm.py``)
run for the same first steps on the same batches, leaf by leaf:

- ``loss_gap``: each checked step's loss, the largest relative gap;
- ``grad_gap``: the norm of the first gradient as the optimizer got it
  (AdamW's first moment after step 1, divided by 1 - b1) against the
  reference's clipped gradient;
- ``update_gap``: the norm of each weight's change over the checked steps.

A norm's gap is ``|program - reference|`` over the larger of the
reference's norm of that leaf and of the median leaf; the worst leaf
counts. Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone and are left out of ``update_gap``.
"""

from __future__ import annotations

import functools
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, weights
from bench.drivers.common import Context, Outcome, dense_model_config, device_now
from bench.reference import lm as ref_lm


class ZipfRows:
    """Batches of token rows from the seed: ids drawn with probability
    proportional to rank ** -exponent (a copy of the program's
    ``SyntheticLM`` distribution); batch ``i`` depends only on (seed, i)."""

    def __init__(self, vocab: int, batch: int, seq: int, exponent: float,
                 seed: int):
        p = 1.0 / np.arange(1, vocab + 1) ** exponent
        self._p = p / p.sum()
        self.vocab, self.batch_size, self.seq, self.seed = (
            vocab, batch, seq, seed)

    def batch(self, index: int) -> dict:
        rng = np.random.default_rng([self.seed, 3, index])
        toks = rng.choice(self.vocab, size=(self.batch_size, self.seq + 1),
                          p=self._p).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                "mask": np.ones((self.batch_size, self.seq), np.float32)}


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def leaf_norms(tree) -> dict:
    return dict(zip(weights.leaf_paths(tree),
                    (float(x) for x in _leaf_norms(tree))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def change_norms(c: dict, seed: int, params) -> dict:
    """Norm of each leaf's change from the seed's initial weights, made
    again one leaf at a time."""
    out = {}
    for path, leaf in zip(weights.leaf_paths(params), jax.tree.leaves(params)):
        first = weights.make_leaf(c, seed, path, leaf.dtype)
        out[path] = float(_diff_norm(leaf, first))
        del first
    return out


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's |program - reference| over max(reference leaf norm,
    median reference leaf norm)."""
    med = statistics.median(ref.values())
    return max(abs(prog[p] - ref[p]) / max(ref[p], med)
               for p in ref if keep is None or p in keep)


def reference_steps(c: dict, seed: int, source, steps: int, rows: int,
                    mode: str = "f32", param_dtype: str = "float32"):
    """The reference's losses, first clipped gradient and weight change
    over ``steps`` steps on ``source``'s first batches, in blocks of
    ``rows`` rows. ``param_dtype`` below float32 keeps the weights and the
    optimizer's moments in it (the control). The moments wait in host
    memory while the gradient is computed, so that weights, moments and
    two gradients never share the chip."""
    o = c["optimizer"]
    params = weights.make_params(c, seed, param_dtype)
    zeros = lambda p: np.zeros(p.shape, param_dtype)
    mu, nu = jax.tree.map(zeros, params), jax.tree.map(zeros, params)
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: ref_lm.loss_fn(c, p, b, mode), has_aux=True))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(gsum, mu, nu, params, count, step):
        g = jax.tree.map(lambda x: x / count, gsum)
        cast = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        p, m, v, clipped = ref_lm.adamw(o, g, cast(mu), cast(nu),
                                        cast(params), step)
        back = lambda t: jax.tree.map(lambda x: x.astype(param_dtype), t)
        return back(p), back(m), back(v), clipped

    losses, first_grad = [], None
    for step in range(steps):
        batch = source.batch(step)
        gsum, total, count = None, 0.0, 0.0
        for lo in range(0, batch["tokens"].shape[0], rows):
            blk = {k: jnp.asarray(v[lo:lo + rows]) for k, v in batch.items()}
            (s, n), g = grad(params, blk)
            total, count = total + float(s), count + float(n)
            gsum = g if gsum is None else add(gsum, g)
            del g
        losses.append(total / count)
        params, mu, nu, clipped = update(gsum, jax.device_put(mu),
                                         jax.device_put(nu), params,
                                         jnp.float32(count), step)
        del gsum
        if step == 0:
            first_grad = leaf_norms(clipped)
        del clipped
        mu, nu = jax.device_get((mu, nu))
    change = change_norms(c, seed, params)
    return losses, first_grad, change


def build(c: dict, seed: int, prefetch: int):
    """The program's model, optimizer settings, compiled step, mesh and a
    started prefetch pipeline over the seed's batches."""
    from repro.data import DataConfig, PrefetchPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_train_step
    from repro.models import build_model
    from repro.optim import OptConfig

    model = build_model(dense_model_config(
        c, param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"],
        remat=c["remat"]))
    oc = OptConfig(**c["optimizer"])
    source = ZipfRows(c["vocab_size"], c["batch"], c["seq"],
                      c["zipf_exponent"], seed)
    dc = DataConfig(seq_len=c["seq"], global_batch=c["batch"],
                    vocab_size=c["vocab_size"], prefetch=prefetch)
    step_fn = jax.jit(make_train_step(model, oc), donate_argnums=(0,))
    return model, oc, source, PrefetchPipeline(source, dc).start(), \
        make_host_mesh(), step_fn


def first_state(c: dict, seed: int, model, mesh):
    """The train state from the seed's weights, placed as ``train.py``
    places it. Call under the mesh's sharding rules."""
    from repro import sharding as shd
    from repro.optim import init_opt_state

    params = weights.make_params(c, seed, c["param_dtype"])
    weights.check_layout(params, jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    state = {"params": params, "opt": init_opt_state(params),
             "step": jnp.zeros((), jnp.int32)}
    del params
    state_sh = shd.named_shardings(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state), mesh)
    return jax.tree.map(jax.device_put, state, state_sh)


def checked_steps(c: dict, seed: int, oc, step, state, n: int):
    """Drive ``state`` through its first ``n`` steps with ``step``; returns
    the state, the steps' losses, the first gradient's leaf norms (from
    AdamW's first moment) and each weight's change after the n steps."""
    losses = []
    for i in range(n):
        state, m = step(state)
        losses.append(float(m["loss"]))
        if i == 0:
            first_grad = {p: v / (1 - oc.b1) for p, v in
                          leaf_norms(state["opt"]["mu"]).items()}
    return state, losses, first_grad, change_norms(c, seed, state["params"])


def compare(losses, first_grad, change, ref) -> dict:
    """The numbers compared, from the program's readings and the
    reference's ``(losses, first_grad, change)``."""
    ref_losses, ref_grad, ref_change = ref
    med = statistics.median(ref_grad.values())
    moving = {p for p, g in ref_grad.items() if g >= 1e-3 * med}
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(losses, ref_losses)),
        "grad_gap": norm_gap(first_grad, ref_grad),
        "update_gap": norm_gap(change, ref_change, moving),
    }


def run(ctx: Context) -> Outcome:
    from repro import sharding as shd

    c, t = ctx.config, ctx.traffic
    model, oc, source, pipe, mesh, step_fn = build(c, ctx.seed, t["prefetch"])
    ctx.mark("build")

    def step(state):
        b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        return step_fn(state, b)

    try:
        with shd.use_sharding_rules(mesh):
            state = jax.block_until_ready(
                first_state(c, ctx.seed, model, mesh))
            ctx.mark("weights+state")
            state, losses, first_grad, change = checked_steps(
                c, ctx.seed, oc, step, state, t["checked_steps"])
            ctx.mark("checked steps")

            ctx.tracer.start()
            ctx.mark("trace start")
            tracing, traced_steps = True, None
            n, t0 = 0, time.perf_counter()
            t_last = t0
            while True:
                with ctx.tracer.span("train.feed"):
                    b = {k: jnp.asarray(v)
                         for k, v in pipe.next_batch().items()}
                with ctx.tracer.span("train.dispatch"):
                    state, m = step_fn(state, b)
                n += 1
                if n % t["log_every"]:
                    continue
                with ctx.tracer.span("train.sync"):
                    float(m["loss"])
                t_last = time.perf_counter()
                if tracing and t_last - t0 >= t["trace_seconds"]:
                    trace_path, traced_steps = ctx.tracer.stop(), n
                    tracing = False
                if t_last - t0 >= ctx.seconds:
                    break
            if tracing:
                trace_path, traced_steps = ctx.tracer.stop(), n
    finally:
        pipe.stop()
    device = device_now(ctx.devices)
    del state, m, b, step_fn

    numbers = compare(losses, first_grad, change, reference_steps(
        c, ctx.seed, source, t["checked_steps"], t["reference_rows"]))
    batch, seq = c["batch"], c["seq"]
    return Outcome(
        attempted=n, failed=0,
        end_to_end={"setup_s": t0 - ctx.t_start,
                    "train_tokens_per_s": n * batch * seq / (t_last - t0)},
        facts={"traced_flops": traced_steps * flops.train_step_flops(
                   c, batch, seq),
               "device_kind": ctx.devices[0].device_kind},
        checks=[(k, v, ctx.limit(k)) for k, v in numbers.items()],
        device=device, trace_path=trace_path)
