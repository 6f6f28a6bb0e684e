"""Pipeline parallelism (GPipe schedule) over a mesh axis.

Opt-in PP for depth scaling past what TP+FSDP covers: the stacked layer
params [L, ...] are split into S contiguous stages sharded over a mesh axis;
activations flow stage-to-stage via ``collective_permute`` while M
microbatches fill the pipe (GPipe: M + S - 1 ticks, bubble fraction
(S-1)/(M+S-1)).

Implementation: one ``shard_map`` over the stage axis. Each stage holds its
local layer slice; at tick t it runs microbatch (t - stage_id) if that index
is live, then shifts its output to the next stage. Stage 0 injects inputs;
the last stage's outputs are psum-broadcast at the end (cheap relative to a
training step; avoidable with stage-local consumers).

This module is deliberately self-contained (body_fn in, outputs out) so any
of the framework's layer bodies — including the photonic-quantized ones —
can ride the pipe. Used by tests/test_pipeline.py (4-device sim) and
available through ``launch.steps`` for depth-dominant configs.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_forward(layer_params: PyTree, x: jnp.ndarray,
                     body_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
                     mesh: Mesh, stage_axis: str,
                     n_microbatches: int) -> jnp.ndarray:
    """Run ``body_fn`` over stacked layers with GPipe staging.

    layer_params: pytree with leading layer axis [L, ...], L % S == 0
    x:            [B, T, D] with B % n_microbatches == 0
    body_fn:      (one-layer params, h) -> h
    Returns [B, T, D] — identical (up to reordering of reductions) to
    ``lax.scan(body_fn, x, layer_params)``.
    """
    n_stages = mesh.shape[stage_axis]
    b = x.shape[0]
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches
    l_total = jax.tree.leaves(layer_params)[0].shape[0]
    assert l_total % n_stages == 0, (l_total, n_stages)

    # [L, ...] -> [S, L/S, ...] so the stage axis can shard dim 0
    staged = jax.tree.map(
        lambda p: p.reshape((n_stages, l_total // n_stages) + p.shape[1:]),
        layer_params)
    xm = x.reshape((n_microbatches, mb) + x.shape[1:])

    other_axes = [a for a in mesh.axis_names if a != stage_axis]

    def stage_fn(p_local, xm_full):
        # p_local: [1, L/S, ...] (stage-sharded); xm_full replicated
        p_local = jax.tree.map(lambda q: q[0], p_local)
        sid = jax.lax.axis_index(stage_axis)
        n_ticks = n_microbatches + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def run_local(h):
            def body(carry, lp):
                return body_fn(lp, carry), None
            out, _ = jax.lax.scan(body, h, p_local)
            return out

        def tick(carry, t):
            buf, acc = carry
            mb_idx = t - sid
            live = (mb_idx >= 0) & (mb_idx < n_microbatches)
            inj = jnp.take(xm_full, jnp.clip(t, 0, n_microbatches - 1),
                           axis=0)
            h_in = jnp.where(sid == 0, inj, buf)
            h_out = run_local(h_in)
            h_out = jnp.where(live[..., None, None, None]
                              if h_out.ndim == 3 else live, h_out,
                              jnp.zeros_like(h_out))
            # last stage banks its result; everyone shifts forward
            acc = jax.lax.cond(
                (sid == n_stages - 1) & live,
                lambda a: a.at[jnp.clip(mb_idx, 0, n_microbatches - 1)]
                .set(h_out),
                lambda a: a, acc)
            buf_next = jax.lax.ppermute(h_out, stage_axis, perm)
            return (buf_next, acc), None

        buf0 = jnp.zeros_like(xm_full[0])
        acc0 = jnp.zeros_like(xm_full)
        (_, acc), _ = jax.lax.scan(tick, (buf0, acc0),
                                   jnp.arange(n_ticks))
        # broadcast the last stage's bank to all stages
        mask = (sid == n_stages - 1).astype(acc.dtype)
        return jax.lax.psum(acc * mask, stage_axis)

    p_specs = jax.tree.map(
        lambda q: P(*((stage_axis,) + (None,) * (q.ndim - 1))), staged)
    out = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(p_specs, P(*((None,) * xm.ndim))),
        out_specs=P(*((None,) * xm.ndim)),
        check_vma=False)(staged, xm)
    return out.reshape(x.shape)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
