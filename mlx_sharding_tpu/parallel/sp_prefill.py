"""Sequence-parallel prefill: long prompts sharded over the ``sp`` axis.

The reference's long-context story is "none" — the whole prompt goes through
every stage in one call with a dense T×T mask (SURVEY §5). The framework's
chunked prefill already bounds memory; this module adds the scaling axis the
reference never had: the prompt's SEQUENCE dim is sharded over ``sp``
devices, each device projects Q/K/V for its local T/S tokens (RoPE at global
positions), attention runs as ring attention (K/V blocks rotate over ICI
with a streaming-softmax accumulator — exact, no T×T anything), and the MLP
halves stay local. One program prefills the entire prompt with per-device
activation memory O(T/S).

The resulting per-layer K/V (already rotated) either all-gathers into the
standard decode cache (default: generation continues on the ordinary
single-device/pipeline decode path) or — ``keep_sharded`` — stays
sequence-sharded and feeds ``parallel.sp_decode``'s distributed decode,
which removes the single-chip KV bound entirely. Contract: bit-compatible
logits with the dense prefill (tested sp=4 vs sp=1 in
tests/test_sp_prefill.py; decode parity in tests/test_sp_decode.py).

Wired through the model-level ``sp_layer``/``sp_groups`` hooks: the Llama
family (default hook pair), Gemma-2 (per-layer sliding/global windows +
logit softcap, window-aware ring block skipping) and DeepSeek-V2 (MLA —
compressed-latent MQA with values_from_k, grouped dense/moe scan).
Architectures without ``supports_sp`` keep the chunked path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mlx_sharding_tpu.cache import KVCache
from mlx_sharding_tpu.parallel.mesh import AXIS_SP
from mlx_sharding_tpu.parallel.ring_attention import ring_attention_local


def supports_sp_prefill(model) -> bool:
    cfg = model.config
    return (
        getattr(model, "supports_sp", False)
        and cfg.is_first_stage
        and cfg.is_last_stage  # needs embed + head in-params
    )


def sp_ring_attn_fn(model):
    """The prefill-side attention injected into ``model.sp_layer``: exact
    ring attention over the sp axis, honoring the model's per-layer options
    (Gemma-2 softcap/window; MLA's values-live-in-keys)."""

    def attn_fn(q, k, v, logit_softcap=None, sliding_window=None,
                values_from_k=None):
        # values_from_k passes straight through: the ring then rotates ONLY
        # the key blocks and slices values per step (half the ICI bytes)
        return ring_attention_local(
            q, k, v, model.scale,
            logit_softcap=logit_softcap, sliding_window=sliding_window,
            values_from_k=values_from_k,
        )

    return attn_fn


def build_sp_prefill(model, mesh: Mesh, gather: bool = True):
    """Returns ``fn(params, tokens (B, T_padded), n_valid) -> (logits (B,V),
    ks, vs)`` where ks/vs are (L, B, T_padded, Hkv, D) K/V — all-gathered
    when ``gather`` (single-device decode cache) or left sequence-sharded
    over sp (``parallel.sp_decode`` keeps them sharded for the whole
    generation). T_padded must divide by the sp size; positions >= n_valid
    are padding (their K/V land in cache rows the decode loop
    overwrites/never attends).
    """

    attn_fn = sp_ring_attn_fn(model)

    def body(params, tokens, n_valid):
        idx = jax.lax.axis_index(AXIS_SP)
        t_local = tokens.shape[1]
        offset = idx * t_local  # global position of this device's first token

        h = model.embed(params, tokens)

        # one scan per structurally distinct layer group (DeepSeek's
        # dense/moe split; [None] = the whole homogeneous stack), cache
        # rows concatenated back in layer order
        ks_groups, vs_groups = [], []
        for g in model.sp_groups():
            stack = params["layers"] if g is None else params["layers"][g]

            def layer_body(h, p, _g=g):
                h, k, v = model.sp_layer(p, h, offset, attn_fn, group=_g)
                return h, (k, v)

            h, (ks, vs) = jax.lax.scan(layer_body, h, stack)
            ks_groups.append(ks)
            vs_groups.append(vs)
        ks = (
            jnp.concatenate(ks_groups, axis=0)
            if len(ks_groups) > 1 else ks_groups[0]
        )
        vs = (
            jnp.concatenate(vs_groups, axis=0)
            if len(vs_groups) > 1 else vs_groups[0]
        )

        # last REAL position lives on device (n_valid-1) // t_local
        local_last = jnp.clip(n_valid - 1 - offset, 0, t_local - 1)
        last = jax.lax.dynamic_index_in_dim(h, local_last, 1, keepdims=False)
        logits = model.apply_head(params, last).astype(jnp.float32)
        owner = (n_valid - 1) // t_local == idx
        logits = jax.lax.psum(jnp.where(owner, logits, 0.0), AXIS_SP)

        if gather:
            # (L, B, T_local, H, D) -> full (L, B, T, H, D) for the decode cache
            ks = jax.lax.all_gather(ks, AXIS_SP, axis=2, tiled=True)
            vs = jax.lax.all_gather(vs, AXIS_SP, axis=2, tiled=True)
        return logits, ks, vs

    seq_spec = P(None, AXIS_SP)
    rep = P()
    kv_out = rep if gather else P(None, None, AXIS_SP)

    def make(params_tree):
        return jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(jax.tree.map(lambda _: rep, params_tree), seq_spec, rep),
                out_specs=(rep, kv_out, kv_out),
                check_vma=False,
            )
        )

    return make


class SpPrefill:
    """Compiled, reusable sequence-parallel prefill for one (model, mesh).

    Built once per Generator (mirrors how ``_prefill`` is jitted once).
    Prompt lengths are bucketed to multiples of ``sp_size * prefill_chunk``
    so the number of distinct compiled shapes stays bounded. Params are
    replicated over the sp mesh ONCE at construction — every sp device needs
    the full weights anyway; the cost is one extra replica on the default
    device next to the generator's own copy.
    """

    def __init__(self, model, params, mesh: Mesh, prefill_chunk: int,
                 keep_sharded: bool = False):
        self.model = model
        self.mesh = mesh
        self.size = mesh.shape[AXIS_SP]
        self.quantum = self.size * prefill_chunk
        self.keep_sharded = keep_sharded
        self._make = build_sp_prefill(model, mesh, gather=not keep_sharded)
        self._fn = None  # shape-polymorphic jit; compiles per T_pad bucket
        self._rep = NamedSharding(mesh, P())
        self._seq = NamedSharding(mesh, P(None, AXIS_SP))
        self.params = jax.device_put(params, self._rep)

        def write(cache, ks, vs, n_valid):
            zero = jnp.zeros((), jnp.int32)
            k = jax.lax.dynamic_update_slice(
                cache.k, ks.astype(cache.k.dtype), (zero,) * cache.k.ndim
            )
            v = jax.lax.dynamic_update_slice(
                cache.v, vs.astype(cache.v.dtype), (zero,) * cache.v.ndim
            )
            return KVCache(k=k, v=v, offset=n_valid)

        self._write = jax.jit(write, donate_argnums=(0,))

    def padded_len(self, t: int) -> int:
        return -(-t // self.quantum) * self.quantum

    def prefill_sharded(self, prompt: np.ndarray):
        """Sharded-mode prefill: returns (logits (B, V) replicated, ks, vs
        (L, B, T_pad, H, D) sequence-sharded over sp). The caller installs
        ks/vs into an sp-sharded decode cache (SpDecode.write_prefill)."""
        t = prompt.shape[1]
        tokens = np.pad(prompt, ((0, 0), (0, self.padded_len(t) - t)))
        if self._fn is None:
            self._fn = self._make(self.params)
        return self._fn(
            self.params,
            jax.device_put(jnp.asarray(tokens), self._seq),
            jax.device_put(jnp.asarray(t, jnp.int32), self._rep),
        )

    def __call__(self, prompt: np.ndarray, cache: KVCache):
        """Prefill ``prompt`` (B, T) into ``cache``; returns (logits, cache).
        Padded K/V rows sit beyond ``offset`` and are never attended (causal
        masking by offset) before being overwritten by decode."""
        t = prompt.shape[1]
        t_pad = self.padded_len(t)
        if t_pad > cache.max_seq:
            raise ValueError(
                f"sp prefill needs {t_pad} cache rows, capacity {cache.max_seq}"
            )
        tokens = np.pad(prompt, ((0, 0), (0, t_pad - t)))
        if self._fn is None:
            self._fn = self._make(self.params)
        logits, ks, vs = self._fn(
            self.params,
            jax.device_put(jnp.asarray(tokens), self._seq),
            jax.device_put(jnp.asarray(t, jnp.int32), self._rep),
        )
        # the gathered K/V is replicated over sp; hand the default device's
        # copy to the single-device decode cache without a host round-trip
        dev = jax.devices()[0]
        cache = self._write(
            cache,
            jax.device_put(ks, dev),
            jax.device_put(vs, dev),
            jax.device_put(jnp.asarray(t, jnp.int32), dev),
        )
        return jax.device_put(logits, dev), cache
