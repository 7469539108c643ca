"""Transformer building blocks: norms, RoPE, GQA attention, SwiGLU MLP.

Everything is a pure function over an explicit params dict.  Activations are
annotated with logical axes (``repro.distributed.sharding.shard``) so the
same code runs unannotated on one device and fully sharded under the
production mesh rules.

Attention supports the assigned archs' flags: GQA (grouped einsum — the
repeated KV heads are never materialized), QKV bias (qwen2), per-head
qk_norm (qwen3), sliding window (mixtral), bidirectional (whisper encoder),
cross-attention with static memory (whisper decoder / llama-vision), and a
ring-buffer KV cache for decode.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard

Params = dict[str, Any]

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w)).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (((x - mu) * jax.lax.rsqrt(var + eps)) * (1.0 + w) + b).astype(dt)


def apply_norm(x: jax.Array, p: Params, name: str, cfg: ModelConfig) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, p[name], p[name + "_b"])
    return rms_norm(x, p[name])


def init_norm(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    out = {"w": jnp.zeros((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        out["b"] = jnp.zeros((d,), jnp.float32)
    return out


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_cos_sin(positions: jax.Array, dim: int, theta: float):
    """positions (..., L) -> cos/sin (..., L, dim/2) in fp32."""
    freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, L, H, Dh); cos/sin (B, L, Dh/2) — rotate-half convention."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(dt)


def cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new`` (B, 1, ...) into ``cache`` (B, S, ...) at row ``pos``.

    Baseline path: one-hot masked select instead of dynamic_update_slice —
    GSPMD cannot partition a DUS whose *traced* offset lands on a sharded
    dim (it replicates the whole cache — measured: 16× decode peak).  The
    masked write stays sharded, but it reads AND rewrites the full cache
    (≈4 extra cache passes per decode step on top of attention's 2 reads).

    Optimized path (§Perf): when the active rules shard the cache's
    sequence dim, ``_cache_write_sharded`` runs a shard_map in which only
    the rank owning slot ``pos`` performs a 1-row local DUS — in-place
    under donation, ~zero extra traffic.  Enabled per-config via
    ``decode_cache_impl="sharded_dus"``.
    """
    from repro.distributed.sharding import active_rules

    r = active_rules()
    impl = getattr(r, "cache_impl", "") if r is not None else ""
    if "sharded_dus" in impl:
        out = _cache_write_sharded(cache, new, pos, r)
        if out is not None:
            return out
    if "heads_dus" in impl:
        # head-sharded cache layout: the seq dim is whole on every rank, so
        # a traced-offset DUS partitions in place (no full-cache rewrite)
        start = (jnp.zeros((), pos.dtype), pos) + (jnp.zeros((), pos.dtype),) * (
            cache.ndim - 2
        )
        return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), start)
    s = cache.shape[1]
    mask = (jnp.arange(s) == pos).reshape((1, s) + (1,) * (cache.ndim - 2))
    return jnp.where(mask, new.astype(cache.dtype), cache)


def _cache_write_sharded(cache, new, pos, rules):
    """1-row local DUS on the rank owning the slot (see cache_write).

    Returns None when the layout doesn't qualify (seq axis unsharded or
    non-divisible) — caller falls back to the masked write.
    """
    from jax.sharding import PartitionSpec as P

    seq_ax = rules.logical.get("kv_seq")
    if not seq_ax:
        return None
    mesh = rules.mesh
    n_seq = mesh.shape[seq_ax] if not isinstance(seq_ax, tuple) else int(
        np.prod([mesh.shape[a] for a in seq_ax])
    )
    if n_seq <= 1 or cache.shape[1] % n_seq:
        return None
    batch_ax = rules.logical.get("batch")
    if batch_ax and cache.shape[0] % (
        int(np.prod([mesh.shape[a] for a in batch_ax]))
        if isinstance(batch_ax, tuple)
        else mesh.shape[batch_ax]
    ):
        batch_ax = None
    trail = (None,) * (cache.ndim - 2)
    c_spec = P(batch_ax, seq_ax, *trail)
    n_spec = P(batch_ax, None, *trail)

    def body(c, n, s):
        idx = jax.lax.axis_index(seq_ax)
        s_local = c.shape[1]
        local = s - idx * s_local
        in_range = (local >= 0) & (local < s_local)
        li = jnp.clip(local, 0, s_local - 1)
        row = jax.lax.dynamic_slice_in_dim(c, li, 1, axis=1)
        row = jnp.where(in_range, n.astype(c.dtype), row)
        return jax.lax.dynamic_update_slice_in_dim(c, row, li, axis=1)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(c_spec, n_spec, P()),
        out_specs=c_spec,
        check_vma=False,
    )(cache, new.astype(cache.dtype), pos)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelConfig, *, cross: bool = False) -> Params:
    d, dh = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    k = iter(jax.random.split(key, 8))
    sd = 1.0 / np.sqrt(d)
    p: Params = {
        "wq": jax.random.normal(next(k), (d, h, dh), jnp.float32) * sd,
        "wo": jax.random.normal(next(k), (h, dh, d), jnp.float32) / np.sqrt(h * dh),
    }
    mem_d = cfg.image_embed_dim if (cross and cfg.family == "vlm") else d
    kname, vname = ("wk_mem", "wv_mem") if cross else ("wk", "wv")
    p[kname] = jax.random.normal(next(k), (mem_d, hkv, dh), jnp.float32) / np.sqrt(mem_d)
    p[vname] = jax.random.normal(next(k), (mem_d, hkv, dh), jnp.float32) / np.sqrt(mem_d)
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h, dh), jnp.float32)
        p["bk"] = jnp.zeros((hkv, dh), jnp.float32)
        p["bv"] = jnp.zeros((hkv, dh), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)  # llama-vision tanh gate
    return p


def _sdpa(
    q: jax.Array,  # (B, Lq, H, Dh)
    k: jax.Array,  # (B, Lk, Hkv, Dh)
    v: jax.Array,  # (B, Lk, Hkv, Dh)
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,
    window: int = 0,
    kv_len: jax.Array | None = None,
) -> jax.Array:
    """Grouped-query scaled dot-product attention (reference path).

    Never materializes repeated KV heads: q is reshaped to (B, Lq, Hkv, G,
    Dh) and scores are computed per kv-head group.  ``q_offset`` is the
    absolute position of q's first row (decode: current position).
    ``kv_len`` masks cache tails beyond the valid length.
    """
    b, lq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, lq, hkv, g, dh)
    scale = 1.0 / np.sqrt(dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale

    qpos = jnp.arange(lq)[:, None] + q_offset          # (Lq, 1) absolute
    kpos = jnp.arange(k.shape[1])[None, :]             # (1, Lk)
    mask = jnp.ones((lq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, lq, h, dh)


def _sdpa_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    kv_len: jax.Array | None = None,
    q_chunk: int = 512,
) -> jax.Array:
    """Query-chunked attention: O(bq·Lk) live scores instead of O(Lq·Lk).

    The XLA-level analogue of the flash kernel's memory behavior — this is
    what the dry-run compiles, so the roofline memory term reflects
    kernel-like (not materialized-S²) attention.  ``lax.scan`` over query
    chunks; K/V stay resident.
    """
    b, lq, h, dh = q.shape
    c = min(q_chunk, lq)
    if lq % c:
        return _sdpa(q, k, v, causal=causal, window=window, kv_len=kv_len)
    nc = lq // c
    qs = jnp.moveaxis(q.reshape(b, nc, c, h, dh), 1, 0)  # (nc, B, c, h, dh)

    def body(_, inp):
        qc, iq = inp
        o = _sdpa(
            qc, k, v, causal=causal, q_offset=iq * c, window=window, kv_len=kv_len
        )
        return None, o

    _, outs = jax.lax.scan(body, None, (qs, jnp.arange(nc)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, lq, h, dh)


_CHUNK_THRESHOLD = 2048


def _sdpa_auto(q, k, v, *, causal, window=0, kv_len=None):
    if q.shape[1] >= _CHUNK_THRESHOLD:
        return _sdpa_chunked(q, k, v, causal=causal, window=window, kv_len=kv_len)
    return _sdpa(q, k, v, causal=causal, window=window, kv_len=kv_len)


def _sdpa_decode_decomposed(
    q: jax.Array,       # (B, 1, H, Dh)
    kc: jax.Array,      # (B, S, Hkv, Dh) cache BEFORE this token's write
    vc: jax.Array,
    kn: jax.Array,      # (B, 1, Hkv, Dh) this token's k/v
    vn: jax.Array,
    *,
    valid_len: jax.Array,   # number of valid cache rows (= pos, or window fill)
    slot: jax.Array,        # ring slot this token will occupy (masked out)
) -> jax.Array:
    """Decode attention over (old cache ⊕ new token) with a joint softmax.

    Mathematically identical to write-then-attend, but nothing reads the
    *updated* cache: the old cache is an attention operand and the 1-row
    write can alias in place (§Perf cell B).  The ring ``slot`` is masked
    from the old cache (it holds the evicted token once the window wraps).
    """
    b, lq, h, dh = q.shape
    hkv = kc.shape[2]
    g = h // hkv
    qg = q.reshape(b, lq, hkv, g, dh)
    scale = 1.0 / np.sqrt(dh)
    s_old = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc).astype(jnp.float32) * scale
    kpos = jnp.arange(kc.shape[1])
    mask = (kpos < valid_len) & (kpos != slot)
    s_old = jnp.where(mask[None, None, None, None], s_old, -1e30)
    s_new = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kn).astype(jnp.float32) * scale
    probs = jax.nn.softmax(jnp.concatenate([s_old, s_new], -1), axis=-1)
    p_old = probs[..., :-1].astype(vc.dtype)
    p_new = probs[..., -1:].astype(vn.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p_old, vc) + jnp.einsum(
        "bhgqk,bkhd->bqhgd", p_new, vn
    )
    return out.reshape(b, lq, h, dh)


def attention(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                  # (B, L, D)
    *,
    positions: jax.Array,          # (B, L) absolute positions
    causal: bool = True,
    cache: Params | None = None,   # {"k","v"} (B, S, Hkv, Dh) ring/linear
    cache_pos: jax.Array | None = None,  # scalar: #tokens already cached
    memory: jax.Array | None = None,     # (B, M, Dm) for cross-attention
) -> tuple[jax.Array, Params | None]:
    """Self/cross attention.  Returns (out (B,L,D), updated cache or None).

    Modes:
      * train/prefill: ``cache is None`` → full self-attention (optionally
        returns no cache; prefill-with-cache uses ``cache`` with
        ``cache_pos=0`` and L ≤ S).
      * decode: L == 1, ``cache_pos`` = current length; KV written at
        ``cache_pos`` (ring position for SWA).
      * cross: ``memory`` supplies K/V (no cache mechanics needed beyond
        one-time projection, passed as ``cache``).
    """
    dh = cfg.resolved_head_dim
    dt = x.dtype
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)

    is_cross = memory is not None or (cache is not None and "k_mem" in cache)
    if is_cross:  # cross-attention path
        if memory is not None:  # train / prefill: project (and cache) memory
            kk = jnp.einsum("bmd,dhk->bmhk", memory, p["wk_mem"].astype(dt))
            vv = jnp.einsum("bmd,dhk->bmhk", memory, p["wv_mem"].astype(dt))
            new_cache = (
                {"k_mem": kk.astype(cache["k_mem"].dtype), "v_mem": vv.astype(cache["v_mem"].dtype)}
                if cache is not None
                else None
            )
        else:  # decode: reuse the projected memory from prefill
            kk, vv = cache["k_mem"].astype(dt), cache["v_mem"].astype(dt)
            new_cache = cache
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
            kk = rms_norm(kk, p["k_norm"])
        out = _sdpa(q, kk, vv, causal=False)
        out = jnp.einsum("blhk,hkd->bld", out, p["wo"].astype(dt))
        if "gate" in p:
            out = jnp.tanh(p["gate"].astype(dt)) * out
        return out, new_cache

    k = jnp.einsum("bld,dhk->blhk", x, p["wk"].astype(dt))
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"].astype(dt))
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])

    cos, sin = rope_cos_sin(positions, dh, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")

    if cache is None:
        out = _sdpa_auto(q, k, v, causal=causal, window=cfg.sliding_window)
        new_cache = None
    else:
        s_max = cache["k"].shape[1]
        if q.shape[1] == 1:  # -------- decode step --------
            from repro.distributed.sharding import active_rules

            r = active_rules()
            impl = getattr(r, "cache_impl", "") if r is not None else ""
            # ring index under SWA, linear otherwise
            slot = cache_pos % s_max if cfg.sliding_window else cache_pos
            if "decomposed" in impl:
                # attend (old cache ⊕ new token); the updated cache is only
                # written, never read — in-place under donation (§Perf)
                valid = (
                    jnp.minimum(cache_pos, s_max)
                    if cfg.sliding_window
                    else cache_pos
                )
                out = _sdpa_decode_decomposed(
                    q, cache["k"], cache["v"], k, v, valid_len=valid, slot=slot
                )
                ck = cache_write(cache["k"], k, slot)
                cv = cache_write(cache["v"], v, slot)
            else:
                ck = cache_write(cache["k"], k, slot)
                cv = cache_write(cache["v"], v, slot)
                if cfg.sliding_window:
                    # every live slot is in-window; mask only unwritten rows
                    valid = jnp.minimum(cache_pos + 1, s_max)
                    out = _sdpa(q, ck, cv, causal=False, kv_len=valid)
                else:
                    out = _sdpa(q, ck, cv, causal=False, kv_len=cache_pos + 1)
            new_cache = {"k": ck, "v": cv}
        else:  # -------- prefill into cache --------
            lq = q.shape[1]
            if cfg.sliding_window and lq > s_max:
                # Only the last window survives; place token t at slot
                # t % s_max so later decode writes stay consistent.
                tail_k = jnp.roll(k[:, -s_max:], lq % s_max, axis=1)
                tail_v = jnp.roll(v[:, -s_max:], lq % s_max, axis=1)
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], tail_k.astype(cache["k"].dtype), (0, 0, 0, 0)
                )
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], tail_v.astype(cache["v"].dtype), (0, 0, 0, 0)
                )
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
                )
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
                )
            out = _sdpa_auto(q, k, v, causal=causal, window=cfg.sliding_window)
            new_cache = {"k": ck, "v": cv}

    out = jnp.einsum("blhk,hkd->bld", out, p["wo"].astype(dt))
    return out, new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(key: jax.Array, cfg: ModelConfig, d_ff: int) -> Params:
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(k1, (d, d_ff), jnp.float32) / np.sqrt(d),
        "w_up": jax.random.normal(k2, (d, d_ff), jnp.float32) / np.sqrt(d),
        "w_down": jax.random.normal(k3, (d_ff, d), jnp.float32) / np.sqrt(d_ff),
    }


def mlp(p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
    h = shard(h, "batch", "seq", "mlp")
    return h @ p["w_down"].astype(dt)
