"""Multi-axis SPMD training: dp × sp × tp (× ep) on one mesh.

This is the framework's flagship composition — the piece SURVEY.md §2.6
lists as out of scope for the *reference* but first-class here: a
transformer whose batch is sharded over ``dp``, sequence over ``sp``
(Ulysses all-to-alls around attention), and weights over ``tp``
(Megatron column/row layers), trained by one compiled shard_map program.
Gradients of replicated parameters are pmean'd over (dp, sp); tp-sharded
parameters train on their local shard — exactly the communication
Megatron+Ulysses prescribe, all derived by XLA's SPMD partitioner from
the same mesh machinery the data-parallel core uses.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import basics
from ..models.transformer import _checkpoint_policy, resolve_remat_policies
from ._mesh_utils import axis_size_or_1 as _axis_size_or_1
from .tensor_parallel import TensorParallelAttention, TensorParallelMlp
from .ulysses import ulysses_attention

DP_AXIS, SP_AXIS, TP_AXIS = "dp", "sp", "tp"


def _make_attn_fn(attention_impl: str, causal: bool,
                  window: Optional[int]) -> Callable:
    """The per-block attention closure of :class:`MultiAxisTransformer`."""

    def attn_fn(q, k, v):
        # SP_AXIS always exists on the (dp, sp, tp) mesh (size 1 when
        # sp folded away, where ulysses degenerates to local
        # attention and the ring to the single-chip kernels); passing
        # None here would make either scheme look for the unbound
        # world axis and crash at sp=1, tp>1
        if attention_impl in ("ring", "ring_flash"):
            from .ring_attention import ring_attention

            return ring_attention(
                q, k, v, axis_name=SP_AXIS,
                impl="flash" if attention_impl == "ring_flash"
                else "dense",
                causal=causal, window=window,
            )
        if attention_impl != "ulysses":
            raise ValueError(
                f"unknown attention_impl {attention_impl!r}; "
                "expected 'ulysses', 'ring' or 'ring_flash'"
            )
        return ulysses_attention(
            q, k, v, axis_name=SP_AXIS, causal=causal, window=window,
        )

    return attn_fn


def multi_axis_mesh(dp: int, sp: int = 1, tp: int = 1,
                    devices=None) -> Mesh:
    """Build the (dp, sp, tp) mesh.  Axis order puts ``tp`` innermost —
    the axis with per-layer collectives rides the fastest ICI links
    (scaling-book mesh-layout recipe)."""
    if devices is None:
        devices = (basics._require_init().topology.devices
                   if basics.is_initialized() else jax.devices())
    n = dp * sp * tp
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(dp, sp, tp)
    return Mesh(arr, (DP_AXIS, SP_AXIS, TP_AXIS))


class _MultiAxisBlock(nn.Module):
    """One pre-norm decoder block of :class:`MultiAxisTransformer` —
    factored out of the layer loop so ``nn.remat`` can lift it per
    block (the configurable activation-remat policies of
    docs/OPTIM.md)."""

    d_model: int
    num_heads: int
    head_dim: int
    dtype: jnp.dtype
    attn_fn: Callable

    @nn.compact
    def __call__(self, x):
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        h = TensorParallelAttention(
            num_heads=self.num_heads, head_dim=self.head_dim,
            axis=TP_AXIS, attn_fn=self.attn_fn, dtype=self.dtype,
            name="attn",
        )(h)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = TensorParallelMlp(
            d_model=self.d_model, d_ff=4 * self.d_model, axis=TP_AXIS,
            dtype=self.dtype, name="mlp",
        )(h)
        return x + h


class MultiAxisTransformer(nn.Module):
    """Decoder-only LM over the (dp, sp, tp) mesh.

    Inside shard_map, inputs arrive as the local (B/dp, S/sp) token
    shard; attention composes TP head-sharding with the selected
    sequence-parallel scheme over ``sp``:

      * ``attention_impl='ulysses'`` (default) — all-to-all re-shards
        sequence↔heads around local attention, so the local head count
        H/tp must divide by sp;
      * ``'ring'`` / ``'ring_flash'`` — the sequence stays sharded and
        K/V rotate over the sp axis (dense einsum blocks or pallas
        flash blocks); no head-divisibility constraint on sp, and
        ``window`` additionally truncates the causal rotation
        (ring_window_steps) — the long-context composition the
        flagship transformer exposes single-axis.

    ``window`` (Mistral sliding window) routes into every impl.

    Param-tree layout: each layer lives under ``block_{i}/{ln1, attn,
    ln2, mlp}`` (the per-block module ``nn.remat`` lifts).  Checkpoints
    from before the remat-policy change (flat ``ln1_{i}``/``attn_{i}``/
    … names) need a one-time key rewrite; ``param_specs`` matches by
    substring and is layout-agnostic.
    """

    vocab: int
    d_model: int
    num_heads: int
    num_layers: int
    seq_len: int  # GLOBAL sequence length
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "ulysses"  # 'ulysses' | 'ring' | 'ring_flash'
    causal: bool = True
    window: Optional[int] = None
    # activation-remat policy per block: None (no remat), a
    # models.transformer.REMAT_POLICIES name for every block, or a
    # num_layers tuple of names (docs/OPTIM.md policy matrix)
    remat_policy: Any = None

    @nn.compact
    def __call__(self, tokens):
        sp = _axis_size_or_1(SP_AXIS)
        sp_idx = jax.lax.axis_index(SP_AXIS) if sp > 1 else 0
        s_local = tokens.shape[1]
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (self.vocab, self.d_model), jnp.float32)
        pos_emb = self.param("pos_embed", nn.initializers.normal(0.02),
                             (self.seq_len, self.d_model), jnp.float32)
        x = emb[tokens].astype(self.dtype)
        offset = sp_idx * s_local
        x = x + jax.lax.dynamic_slice_in_dim(
            pos_emb, offset, s_local, axis=0
        ).astype(self.dtype)[None]

        head_dim = self.d_model // self.num_heads
        attn_fn = _make_attn_fn(
            self.attention_impl, self.causal, self.window
        )

        policies = resolve_remat_policies(
            self.remat_policy, self.num_layers
        )
        block_cls_for = {"none": _MultiAxisBlock}
        for i in range(self.num_layers):
            pol = policies[i]
            block_cls = block_cls_for.get(pol)
            if block_cls is None:
                block_cls = nn.remat(
                    _MultiAxisBlock, policy=_checkpoint_policy(pol)
                )
                block_cls_for[pol] = block_cls
            x = block_cls(
                d_model=self.d_model, num_heads=self.num_heads,
                head_dim=head_dim, dtype=self.dtype, attn_fn=attn_fn,
                name=f"block_{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        return jnp.dot(x, emb.T.astype(self.dtype))  # tied head




def param_specs(params: Any) -> Any:
    """PartitionSpec tree for the model's params: Megatron layout —
    column kernels sharded on the output dim, row kernels on the input
    dim, everything else replicated."""

    def spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        joined = "/".join(str(n) for n in names)
        if leaf.ndim == 2:
            if "qkv" in joined or "wi" in joined:
                return P(None, TP_AXIS)  # column-parallel
            if "proj" in joined or "wo" in joined:
                return P(TP_AXIS, None)  # row-parallel
        if leaf.ndim == 1 and ("wi/bias" in joined):
            return P(TP_AXIS)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def init_sharded(model: MultiAxisTransformer, mesh: Mesh, rng,
                 local_batch: int = 1) -> Any:
    """Initialize params already laid out on the mesh.

    Replicated leaves must be identical on every chip (they draw from the
    shared base rng), while tp-sharded leaves are DISTINCT shards of a
    conceptually larger matrix — they draw from an rng folded with this
    chip's tp index, the Megatron per-partition init.  (A single shared
    rng would make all tp shards bit-identical, and gradient symmetry
    would keep them identical forever — silently wasting 1/tp of model
    capacity.)"""
    sp = mesh.shape[SP_AXIS]
    s_local = model.seq_len // sp
    tokens = jnp.zeros((local_batch, s_local), jnp.int32)

    def plain_init(rng, tokens):
        return model.init(rng, tokens)

    abstract = jax.eval_shape(
        lambda r, t: jax.shard_map(
            plain_init, mesh=mesh, in_specs=(P(), P()),
            out_specs=P(), check_vma=False,
        )(r, t), rng, tokens,
    )
    specs = {"params": param_specs(abstract["params"])}

    def init_fn(rng, tokens):
        base = model.init(rng, tokens)
        tp_rng = jax.random.fold_in(rng, jax.lax.axis_index(TP_AXIS))
        folded = model.init(tp_rng, tokens)

        picked = jax.tree_util.tree_map(
            lambda spec, b, f: f if TP_AXIS in spec else b,
            specs["params"], base["params"], folded["params"],
            is_leaf=lambda x: isinstance(x, P),
        )
        return {"params": picked}

    out = jax.jit(jax.shard_map(
        init_fn, mesh=mesh, in_specs=(P(), P()), out_specs=specs,
        check_vma=False,
    ))(rng, tokens)
    return out, specs


def _flatten_with_str_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        keys = tuple(
            str(getattr(p, "key", getattr(p, "name", p))) for p in path
        )
        out.append((keys, leaf))
    return out


def opt_state_specs(optimizer: optax.GradientTransformation, params: Any,
                    pspecs: Any) -> Any:
    """PartitionSpec tree for the optimizer state: optax states embed
    params-shaped subtrees (momentum, adam moments, ...) whose tree paths
    END with the parameter's path — match by path suffix + shape and
    inherit the parameter's spec; everything else (counts, scalars) is
    replicated."""
    abstract = jax.eval_shape(optimizer.init, params)
    spec_by_path = {
        path: spec for path, spec in _flatten_with_str_paths(pspecs)
    }
    shape_by_path = {
        path: leaf.shape for path, leaf in _flatten_with_str_paths(params)
    }

    def assign(path, leaf):
        keys = tuple(
            str(getattr(p, "key", getattr(p, "name", p))) for p in path
        )
        for ppath, spec in spec_by_path.items():
            if len(keys) >= len(ppath) and keys[-len(ppath):] == ppath \
                    and shape_by_path[ppath] == leaf.shape:
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(assign, abstract)


def init_opt_sharded(optimizer: optax.GradientTransformation, params: Any,
                     mesh: Mesh, pspecs: Any) -> Tuple[Any, Any]:
    """Initialize the optimizer state with the mesh layout matching the
    (possibly tp-sharded) params."""
    ospecs = opt_state_specs(optimizer, params, pspecs)
    opt_state = jax.jit(jax.shard_map(
        optimizer.init, mesh=mesh, in_specs=(pspecs,), out_specs=ospecs,
        check_vma=False,
    ))(params)
    return opt_state, ospecs


def make_sharded_train_step(model: MultiAxisTransformer,
                            optimizer: optax.GradientTransformation,
                            mesh: Mesh, param_spec_tree: Any,
                            opt_spec_tree: Any):
    """One compiled program: forward (TP × SP), backward, grad pmean over
    (dp, sp), optimizer update — the multi-axis analog of
    training.data_parallel_train_step."""

    def step(params, opt_state, tokens, targets):
        def loss_fn(p):
            logits = model.apply(p, tokens)
            losses = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), targets
            )
            return losses.mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # replicated across dp and sp -> average gradients over both;
        # tp-sharded leaves hold distinct shards and are NOT tp-reduced
        grads = jax.lax.pmean(grads, (DP_AXIS, SP_AXIS))
        loss = jax.lax.pmean(loss, (DP_AXIS, SP_AXIS))
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    pspecs = param_spec_tree
    ospecs = opt_spec_tree
    data_spec = P(DP_AXIS, SP_AXIS)
    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, ospecs, data_spec, data_spec),
        out_specs=(pspecs, ospecs, P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1))
