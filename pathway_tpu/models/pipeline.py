"""Pipeline-parallel encoder forward (GPipe schedule over a ``pp`` mesh
axis).

The layer stack shards across pipeline stages (each device holds
``layers / pp`` consecutive layers); microbatches stream through the
stages, activations hopping stage-to-stage over ICI with ``ppermute``.
The schedule runs ``n_micro + pp - 1`` ticks; stage 0 ingests a new
microbatch each tick while the last stage retires finished ones into the
output buffer, which a final ``psum`` replicates. Exact — the result is
bit-comparable to the sequential ``encode``.

The reference has no pipeline parallelism (SURVEY §2.11); this extends the
flagship family's scaling axes (dp/tp/sp/ep/pp) beyond it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pathway_tpu.models.transformer import (
    TransformerConfig,
    _layer,
    embed_inputs,
)


def encode_pipelined(params: dict, input_ids: jax.Array,
                     attention_mask: jax.Array, cfg: TransformerConfig,
                     mesh: Mesh, n_microbatches: int = 2,
                     token_type_ids: jax.Array | None = None) -> jax.Array:
    """Encoder forward with the layer stack pipelined over the mesh's
    ``pp`` axis. ``input_ids``/``attention_mask``: (B, S); B must divide
    into ``n_microbatches``. Returns (B, S, H) float32."""
    pp = mesh.shape["pp"]
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    if L % pp:
        raise ValueError(
            f"the pp axis ({pp}) must divide the layer count ({L})"
        )
    B, S = input_ids.shape
    if B % n_microbatches:
        raise ValueError(
            f"n_microbatches ({n_microbatches}) must divide the batch ({B})"
        )
    mb = B // n_microbatches

    # embeddings + final reshape are replicated host-side of the pipeline:
    # only the layer stack is staged
    x, mask_bias = embed_inputs(params, input_ids, attention_mask, cfg,
                                token_type_ids)

    xs = x.reshape(n_microbatches, mb, S, cfg.hidden)
    biases = mask_bias.reshape(n_microbatches, mb, 1, 1, S)

    n_micro = n_microbatches
    n_ticks = n_micro + pp - 1

    def stage_body(local_layers, xs_local, biases_local):
        """Per-device pipeline schedule (runs under shard_map on 'pp')."""
        idx = jax.lax.axis_index("pp")
        n_stages = jax.lax.psum(1, "pp")

        def run_stage(x, bias):
            def body(carry, lp):
                return _layer(carry, lp, bias, cfg), None

            y, _ = jax.lax.scan(body, x, local_layers)
            return y

        def tick(carry, t):
            cur, cur_bias, outputs = carry
            # stage 0 ingests microbatch t (clamped; masked off past the end)
            m_in = jnp.clip(t, 0, n_micro - 1)
            fresh = xs_local[m_in]
            fresh_bias = biases_local[m_in]
            x_in = jnp.where(idx == 0, fresh, cur)
            b_in = jnp.where(idx == 0, fresh_bias, cur_bias)
            y = run_stage(x_in.astype(cfg.dtype), b_in)
            # retire: the LAST stage's output at tick t is microbatch
            # m = t - (pp - 1)
            m_out = t - (n_stages - 1)
            write = (idx == n_stages - 1) & (m_out >= 0)
            updated = jax.lax.dynamic_update_slice(
                outputs,
                y.astype(jnp.float32)[None],
                (jnp.clip(m_out, 0, n_micro - 1), 0, 0, 0),
            )
            outputs = jnp.where(write, updated, outputs)
            # hop activations (and their masks) to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(y, "pp", perm)
            nxt_bias = jax.lax.ppermute(b_in, "pp", perm)
            return (nxt, nxt_bias, outputs), None

        # initial carries must be marked pp-varying: they flow through
        # ppermute / per-stage writes, which produce varying values
        def varying(a):
            return jax.lax.pcast(a, ("pp",), to="varying")

        cur0 = varying(jnp.zeros((mb, S, cfg.hidden), cfg.dtype))
        bias0 = varying(jnp.zeros((mb, 1, 1, S), jnp.float32))
        outputs0 = varying(jnp.zeros((n_micro, mb, S, cfg.hidden), jnp.float32))
        (_, _, outputs), _ = jax.lax.scan(
            tick, (cur0, bias0, outputs0), jnp.arange(n_ticks)
        )
        # outputs are populated only on the last stage; psum replicates
        return jax.lax.psum(outputs, "pp")

    staged = jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(P("pp"), P(), P()),
        out_specs=P(),
        check_vma=False,
    )(params["layers"], xs, biases)
    return staged.reshape(B, S, cfg.hidden).astype(jnp.float32)
