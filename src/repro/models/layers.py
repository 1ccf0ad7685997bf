"""Transformer building blocks: norms, RoPE, GQA attention, MLP, MoE.

Functional style: ``init_*`` returns a param dict; ``apply`` functions are
pure.  Activations carry logical sharding annotations via
``repro.sharding.rules.constrain`` (no-ops outside a mesh context).

Attention is blockwise (online softmax) in XLA — the dry-run-compilable
path — with the Pallas flash kernel as the TPU production path selected by
``repro.kernels.ops``.  GQA is handled natively (KV never repeated).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.rules import constrain


def _dtype(name: str):
    return dict(float32=jnp.float32, bfloat16=jnp.bfloat16,
                float16=jnp.float16,
                float8_e4m3fn=jnp.float8_e4m3fn)[name]


def dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0] if len(shape) >= 1 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., s, h, dh); positions (..., s) or (s,)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs[None, :]
    cos = jnp.cos(ang)[..., :, None, :]     # (..., s, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (blockwise, XLA) — prefill/train path
# ---------------------------------------------------------------------------

def _fa_blocks(k, v, block_k):
    b, sk, hk, dh = k.shape
    nblk = -(-sk // block_k)
    pad = nblk * block_k - sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = kp.reshape(b, nblk, block_k, hk, dh).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, nblk, block_k, hk, dh).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(nblk * block_k).reshape(nblk, block_k)
    return kb.astype(jnp.float32), vb.astype(jnp.float32), kpos


def _fa_forward(q, k, v, causal, block_k, q_offset):
    """Online-softmax forward.  Returns (out_f32 (b,sq,g,hk,dh), m, l)."""
    b, sq, h, dh = q.shape
    sk, hk = k.shape[1], k.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, hk, g, dh)
    kb, vb, kpos = _fa_blocks(k, v, block_k)
    qpos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kblk, vblk, kp_blk = inp
        s = jnp.einsum("bqkgd,bckd->bqgkc", qf, kblk)   # (b,sq,g,hk,block)
        mask = kp_blk[None, :] < sk
        if causal:
            mask = mask & (kp_blk[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bqgkc,bckd->bqgkd", p, vblk)
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((b, sq, g, hk), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, sq, g, hk), jnp.float32)
    acc0 = jnp.zeros((b, sq, g, hk, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, kpos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def blockwise_gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            causal: bool = True, block_k: int = 1024,
                            q_offset: int = 0) -> jax.Array:
    """FlashAttention in XLA with a block-recomputing backward (custom_vjp).

    q (b, sq, h, dh); k/v (b, sk, hk, dh), h % hk == 0 (GQA native — KV is
    never repeated).  Neither pass materializes (sq, sk): the forward is an
    online-softmax scan over KV blocks; the backward recomputes each block's
    probabilities from the saved (m, l) statistics — the standard flash
    backward, which is what keeps train_4k activation memory linear in S.
    """
    out, _, _ = _fa_forward(q, k, v, causal, block_k, q_offset)
    b, sq, h, dh = q.shape
    # out is (b, sq, g, hk, dh); input head order is (hk, g)
    return out.transpose(0, 1, 3, 2, 4).reshape(b, sq, h, dh).astype(q.dtype)


def _fa_vjp_fwd(q, k, v, causal, block_k, q_offset):
    out, m, l = _fa_forward(q, k, v, causal, block_k, q_offset)
    b, sq, h, dh = q.shape
    return (out.transpose(0, 1, 3, 2, 4).reshape(b, sq, h, dh).astype(q.dtype),
            (q, k, v, out, m, l))


def _fa_vjp_bwd(causal, block_k, q_offset, res, dout):
    q, k, v, out, m, l = res
    b, sq, h, dh = q.shape
    sk, hk = k.shape[1], k.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, hk, g, dh)
    do = dout.astype(jnp.float32).reshape(b, sq, hk, g, dh)
    kb, vb, kpos = _fa_blocks(k, v, block_k)
    qpos = q_offset + jnp.arange(sq)
    lsafe = jnp.maximum(l, 1e-30)
    # D = rowsum(dout * out)  (out here is the normalized f32 output)
    D = jnp.sum(do.transpose(0, 1, 3, 2, 4) * out, axis=-1)  # (b,sq,g,hk)

    def step(dq_acc, inp):
        kblk, vblk, kp_blk = inp
        s = jnp.einsum("bqkgd,bckd->bqgkc", qf, kblk)
        mask = kp_blk[None, :] < sk
        if causal:
            mask = mask & (kp_blk[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        p = jnp.exp(s - m[..., None]) / lsafe[..., None]      # (b,q,g,hk,c)
        dv_blk = jnp.einsum("bqgkc,bqkgd->bckd", p, do)
        dp = jnp.einsum("bqkgd,bckd->bqgkc", do, vblk)
        ds = p * (dp - D[..., None])                          # (b,q,g,hk,c)
        dq_blk = jnp.einsum("bqgkc,bckd->bqkgd", ds, kblk) * scale
        dk_blk = jnp.einsum("bqgkc,bqkgd->bckd", ds, qf)  # qf carries scale
        return dq_acc + dq_blk, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, hk, g, dh), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (kb, vb, kpos))
    nblk = kb.shape[0]
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(b, nblk * block_k, hk, dh)
    dv = dv_b.transpose(1, 0, 2, 3, 4).reshape(b, nblk * block_k, hk, dh)
    return (dq.reshape(b, sq, h, dh).astype(q.dtype),
            dk[:, :sk].astype(k.dtype), dv[:, :sk].astype(v.dtype))


blockwise_gqa_attention.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)


def flash_attention_xla(q, k, v, causal=True, *, block_q: int = 1024,
                        block_k: int = 512, q_offset: int = 0):
    """Query-and-key tiled flash attention (XLA scan over q chunks).

    Bounds live score memory to (block_q x block_k) per step in both passes;
    dk/dv accumulate across q chunks via the scan transpose.
    """
    b, sq, h, dh = q.shape
    if sq <= block_q:
        return blockwise_gqa_attention(q, k, v, causal, min(block_k,
                                       max(k.shape[1], 1)), q_offset)
    nq = -(-sq // block_q)
    pad = nq * block_q - sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qc = qp.reshape(b, nq, block_q, h, dh).transpose(1, 0, 2, 3, 4)
    # per-chunk position offsets, scanned (f32 so the custom_vjp can emit a
    # zero cotangent); one HLO body regardless of nq.
    offs = (q_offset + jnp.arange(nq) * block_q).astype(jnp.float32)
    outs = jax.lax.map(
        lambda args: _fa_offset_attention(args[0], k, v, causal, block_k,
                                          args[1]),
        (qc, offs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * block_q, h, dh)
    return out[:, :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fa_offset_attention(q, k, v, causal, block_k, q_offset):
    out, _, _ = _fa_forward_dyn(q, k, v, causal, block_k, q_offset)
    b, sq, h, dh = q.shape
    return out.transpose(0, 1, 3, 2, 4).reshape(b, sq, h, dh).astype(q.dtype)


def _fa_forward_dyn(q, k, v, causal, block_k, q_offset):
    """_fa_forward with a *traced* q_offset (for q-chunked scans)."""
    b, sq, h, dh = q.shape
    sk, hk = k.shape[1], k.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, hk, g, dh)
    kb, vb, kpos = _fa_blocks(k, v, block_k)
    qpos = q_offset.astype(jnp.int32) + jnp.arange(sq)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kblk, vblk, kp_blk = inp
        s = jnp.einsum("bqkgd,bckd->bqgkc", qf, kblk)
        mask = kp_blk[None, :] < sk
        if causal:
            mask = mask & (kp_blk[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        m_cur = jnp.maximum(m_cur, -1e30)   # fully-masked rows stay finite
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bqgkc,bckd->bqgkd", p, vblk)
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((b, sq, g, hk), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, sq, g, hk), jnp.float32)
    acc0 = jnp.zeros((b, sq, g, hk, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, kpos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out, m, l


def _fa_dyn_fwd(q, k, v, causal, block_k, q_offset):
    out, m, l = _fa_forward_dyn(q, k, v, causal, block_k, q_offset)
    b, sq, h, dh = q.shape
    return (out.transpose(0, 1, 3, 2, 4).reshape(b, sq, h, dh).astype(q.dtype),
            (q, k, v, out, m, l, q_offset))


def _fa_dyn_bwd(causal, block_k, res, dout):
    q, k, v, out, m, l, q_offset = res
    b, sq, h, dh = q.shape
    sk, hk = k.shape[1], k.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, hk, g, dh)
    do = dout.astype(jnp.float32).reshape(b, sq, hk, g, dh)
    kb, vb, kpos = _fa_blocks(k, v, block_k)
    qpos = q_offset.astype(jnp.int32) + jnp.arange(sq)
    lsafe = jnp.maximum(l, 1e-30)
    D = jnp.sum(do.transpose(0, 1, 3, 2, 4) * out, axis=-1)

    def step(dq_acc, inp):
        kblk, vblk, kp_blk = inp
        s = jnp.einsum("bqkgd,bckd->bqgkc", qf, kblk)
        mask = kp_blk[None, :] < sk
        if causal:
            mask = mask & (kp_blk[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        p = jnp.exp(s - m[..., None]) / lsafe[..., None]
        dv_blk = jnp.einsum("bqgkc,bqkgd->bckd", p, do)
        dp = jnp.einsum("bqkgd,bckd->bqgkc", do, vblk)
        ds = p * (dp - D[..., None])
        dq_blk = jnp.einsum("bqgkc,bckd->bqkgd", ds, kblk) * scale
        dk_blk = jnp.einsum("bqgkc,bqkgd->bckd", ds, qf)
        return dq_acc + dq_blk, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, hk, g, dh), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (kb, vb, kpos))
    nblk = kb.shape[0]
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(b, nblk * block_k, hk, dh)
    dv = dv_b.transpose(1, 0, 2, 3, 4).reshape(b, nblk * block_k, hk, dh)
    return (dq.reshape(b, sq, h, dh).astype(q.dtype),
            dk[:, :sk].astype(k.dtype), dv[:, :sk].astype(v.dtype),
            jnp.zeros_like(q_offset))


_fa_offset_attention.defvjp(_fa_dyn_fwd, _fa_dyn_bwd)


def _decode_attention_cp(q, k_cache, v_cache, length, rules):
    """Explicit context-parallel flash-decode via shard_map.

    The cache's seq dim is sharded over 'model'; each rank attends over its
    local span and the softmax statistics merge with pmax/psum (log-sum-exp
    combine).  A scan/reshape formulation lets GSPMD serialize or replicate
    the cache across ranks (observed as 'involuntary full rematerialization'
    — §Perf iteration 11); shard_map pins the local-compute + tiny-merge
    structure explicitly."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    b, _, h, dh = q.shape
    S, hk = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(dh)
    ax = rules.rules.get("kv_seq")
    m_size = rules.axis_size(ax)
    batch_ax = rules.rules.get("batch")
    b_ax = batch_ax if (b % rules.axis_size(batch_ax) == 0) else None
    S_loc = S // m_size

    def inner(qv, kl, vl, ln):
        # qv (b_l, 1, h, dh); kl/vl (b_l, S_loc, hk, dh); ln ()
        idx = jax.lax.axis_index(ax)
        pos = idx * S_loc + jnp.arange(S_loc)
        qf = (qv.astype(jnp.float32) * scale).reshape(-1, hk, g, dh)
        s = jnp.einsum("bkgd,bskd->bkgs", qf, kl.astype(jnp.float32))
        mask = pos[None, None, None, :] < ln
        s = jnp.where(mask, s, -jnp.inf)
        m_loc = jnp.maximum(s.max(-1), -1e30)           # (b_l, hk, g)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = jnp.where(mask.any(-1), p.sum(-1), 0.0)
        acc = jnp.einsum("bkgs,bskd->bkgd", p * mask, vl.astype(jnp.float32))
        m_g = jax.lax.pmax(m_loc, ax)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, ax)
        acc_g = jax.lax.psum(acc * corr[..., None], ax)
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        return out.reshape(-1, 1, h, dh).astype(qv.dtype)

    return shard_map(
        inner, mesh=rules.mesh,
        in_specs=(P(b_ax), P(b_ax, ax), P(b_ax, ax), P()),
        out_specs=P(b_ax), check_vma=False,
    )(q, k_cache, v_cache,
      jnp.asarray(length, jnp.int32))


def decode_gqa_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         length: jax.Array | int, *,
                         block_s: int = 4096) -> jax.Array:
    """Single-token decode: q (b, 1, h, dh); caches (b, S, hk, dh).

    Blockwise over the cache sequence with online softmax: the low-precision
    cache (bf16 / fp8) is upcast one block at a time — a monolithic
    ``cache.astype(f32)`` materializes the whole cache again in f32, which
    dominated decode_32k memory (EXPERIMENTS.md §Perf iteration 4).

    The cache's sequence dim may be sharded over the 'model' axis (context
    parallelism): the running max/sum reductions become cross-shard
    collectives inserted by GSPMD — the distributed flash-decode pattern.
    """
    b, _, h, dh = q.shape
    S, hk = k_cache.shape[1], k_cache.shape[2]
    from repro.sharding.rules import active_rules
    r = active_rules()
    if r is not None:
        ax = r.rules.get("kv_seq")
        ms = r.axis_size(ax)
        if isinstance(ax, str) and ms > 1 and S % ms == 0 and S >= 8 * ms:
            return _decode_attention_cp(q, k_cache, v_cache, length, r)
    g = h // hk
    scale = 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).reshape(b, hk, g, dh)

    if S <= block_s:
        kb = k_cache[:, None]
        vb = v_cache[:, None]
        nb, bs = 1, S
    else:
        nb = -(-S // block_s)
        pad = nb * block_s - S
        kb = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) \
            .reshape(b, nb, block_s, hk, dh)
        vb = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) \
            .reshape(b, nb, block_s, hk, dh)
        bs = block_s
    kpos = jnp.arange(nb * bs).reshape(nb, bs)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kblk, vblk, pos = inp                          # (b,bs,hk,dh), (bs,)
        s = jnp.einsum("bkgd,bskd->bkgs", qf, kblk.astype(jnp.float32))
        mask = pos[None, None, None, :] < length
        s = jnp.where(mask, s, -jnp.inf)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        m_cur = jnp.maximum(m_cur, -1e30)
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgs,bskd->bkgd", p, vblk.astype(jnp.float32))
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((b, hk, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hk, g), jnp.float32)
    acc0 = jnp.zeros((b, hk, g, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4), kpos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, 1, h, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (self or cross)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig, dtype, *, n_heads=None,
                   n_kv_heads=None):
    h = n_heads or cfg.n_heads
    hk = n_kv_heads or cfg.n_kv_heads
    d, dh = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, h, dh), dtype),
        "wk": dense_init(ks[1], (d, hk, dh), dtype),
        "wv": dense_init(ks[2], (d, hk, dh), dtype),
        "wo": dense_init(ks[3], (h, dh, d), dtype,
                         scale=1.0 / math.sqrt(h * dh * 2 * cfg.n_layers)),
        "ln": rmsnorm_init(d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((hk, dh), dtype)
        p["bv"] = jnp.zeros((hk, dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(dh, dtype)
        p["k_norm"] = rmsnorm_init(dh, dtype)
    return p


def _qkv(params, cfg: ModelConfig, x, kv_x=None):
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"][None, None]
        k = k + params["bk"][None, None]
        v = v + params["bv"][None, None]
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _maybe_flatten_gqa(k, v, h):
    """Repeat KV to full q-heads when q-heads shard over 'model' but the
    (hk, g) factorization would break sharding propagation.

    GSPMD cannot re-split a 16-way head sharding across an (hk=8, g=2)
    reshape and falls back to full replication ("involuntary full
    rematerialization" — the dominant collective term in the baseline
    roofline; §Perf iteration 10).  With KV repeated, attention stays in
    flat-head layout and every tensor keeps its 'model' sharding."""
    from repro.sharding.rules import active_rules
    r = active_rules()
    if r is None:
        return k, v
    axs = r.axis_size(r.rules.get("heads"))
    hk = k.shape[2]
    # g <= 4 only: at g = 8 the repeated KV is 8x the compact cache and the
    # seq-unshard gathers on it cost more than the (hk, g)-reshape
    # replication it avoids (measured: llama-90b train all-gather body
    # bytes 4.6G -> 23.9G with flat-head at g=8; §Perf iteration 13).
    if axs > 1 and h % axs == 0 and hk % axs != 0 and h != hk \
            and h // hk <= 4:
        g = h // hk
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = constrain(k, ("batch", "seq", "heads", None))
        v = constrain(v, ("batch", "seq", "heads", None))
    return k, v


def self_attention(params, cfg: ModelConfig, x, *, causal=True,
                   positions=None):
    """Full-sequence self-attention (train / encoder / prefill core)."""
    xn = rmsnorm(params["ln"], x, cfg.norm_eps)
    q, k, v = _qkv(params, cfg, xn)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    if cfg.pos_emb == "rope":
        pos = positions if positions is not None else jnp.arange(x.shape[1])
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    kv_cache = (k, v)          # cache keeps the compact GQA layout
    k, v = _maybe_flatten_gqa(k, v, q.shape[2])
    out = flash_attention_xla(q, k, v, causal)
    out = constrain(out, ("batch", "seq", "heads", None))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, ("batch", "residual_seq", "d_model")), kv_cache


def cross_attention(params, cfg: ModelConfig, x, memory):
    """Cross-attention to a (b, m, d) memory (whisper decoder / VLM)."""
    xn = rmsnorm(params["ln"], x, cfg.norm_eps)
    q, k, v = _qkv(params, cfg, xn, kv_x=memory)
    out = flash_attention_xla(q, k, v, False)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, ("batch", "residual_seq", "d_model")), (k, v)


def decode_self_attention(params, cfg: ModelConfig, x, cache_k, cache_v,
                          length, kv_decoder=None):
    """One-token decode against a (b, S, hk, dh) cache; writes slot ``length``.

    ``kv_decoder`` (a ``repro.decode.LSHDecoder`` over this layer's cache,
    optional) swaps the dense cache scan for LSH sparse decode: the new
    key is upserted into the decoder's ``KVCacheIndex`` and attention runs
    over the retrieved ∪ window ∪ sink set.  The decoder mutates host
    state, so this path is host-loop only — do not jit/scan over it (the
    default dense path stays fully traceable).
    """
    xn = rmsnorm(params["ln"], x, cfg.norm_eps)
    q, k, v = _qkv(params, cfg, xn)
    if cfg.pos_emb == "rope":
        pos = jnp.full((1,), length, jnp.int32)
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), length, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), length, axis=1)
    if kv_decoder is not None:
        out = kv_decoder.step(q, cache_k, cache_v, k[:, 0], length + 1)
    else:
        out = decode_gqa_attention(q, cache_k, cache_v, length + 1)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, cache_k, cache_v


def decode_cross_attention(params, cfg: ModelConfig, x, mem_k, mem_v):
    """Decode-time cross-attention against precomputed memory KV."""
    xn = rmsnorm(params["ln"], x, cfg.norm_eps)
    q, _, _ = _qkv(params, cfg, xn)   # memory K/V precomputed at prefill
    out = decode_gqa_attention(q, mem_k, mem_v, mem_k.shape[1])
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, dtype, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "ln": rmsnorm_init(d, dtype),
        "w_gate": dense_init(ks[0], (d, f), dtype),
        "w_up": dense_init(ks[1], (d, f), dtype),
        "w_down": dense_init(ks[2], (f, d), dtype,
                             scale=1.0 / math.sqrt(f * 2 * cfg.n_layers)),
    }


def mlp(params, cfg: ModelConfig, x):
    xn = rmsnorm(params["ln"], x, cfg.norm_eps)
    g = jnp.einsum("bsd,df->bsf", xn, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", xn, params["w_up"])
    h = jax.nn.silu(g) * u
    h = constrain(h, ("batch", "seq", "d_ff"))
    y = jnp.einsum("bsf,fd->bsd", h, params["w_down"])
    return constrain(y, ("batch", "residual_seq", "d_model"))


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-dropping, sort-based grouped matmul)
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig, dtype):
    d, f, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "ln": rmsnorm_init(d, dtype),
        "router": dense_init(ks[0], (d, E), dtype, scale=0.02),
        "we_gate": dense_init(ks[1], (E, d, f), dtype),
        "we_up": dense_init(ks[2], (E, d, f), dtype),
        "we_down": dense_init(ks[3], (E, f, d), dtype,
                              scale=1.0 / math.sqrt(f * 2 * cfg.n_layers)),
    }


def _moe_local_dispatch(xt, router, we_gate, we_up, we_down, E, k, C, *,
                        axis=None):
    """Routed FFN on a flat (T, d) token block with per-expert capacity C.

    With ``axis`` set (inside shard_map), the expert dim is exchanged via
    all_to_all so each rank computes only E/ranks experts over all ranks'
    dispatched tokens (expert parallelism), then a second all_to_all
    returns the outputs.
    """
    T, d = xt.shape
    logits = jnp.einsum("td,de->te", xt, router).astype(jnp.float32)
    gate_all = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(gate_all, k)             # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = eids.reshape(-1)                            # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    starts = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * k) - starts[se]                 # rank within expert
    keep = pos < C
    pos_c = jnp.clip(pos, 0, C - 1)

    buf = jnp.zeros((E, C, d), xt.dtype)
    buf = buf.at[se, pos_c].add(jnp.where(keep[:, None], xt[st], 0))

    if axis is not None:
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=True)             # (E_loc, C*m, d)
    else:
        buf = constrain(buf, ("experts", None, None))
    h_g = jnp.einsum("ecd,edf->ecf", buf, we_gate)
    h_u = jnp.einsum("ecd,edf->ecf", buf, we_up)
    h = jax.nn.silu(h_g) * h_u
    out_buf = jnp.einsum("ecf,efd->ecd", h, we_down)
    if axis is not None:
        out_buf = jax.lax.all_to_all(out_buf, axis, split_axis=1,
                                     concat_axis=0, tiled=True)  # (E, C, d)
    else:
        out_buf = constrain(out_buf, ("experts", None, None))

    contrib = out_buf[se, pos_c] * (sg * keep)[:, None]
    y = jnp.zeros((T, d), xt.dtype).at[st].add(contrib.astype(xt.dtype))
    aux = moe_load_balance_loss(gate_all, eids, E)
    return y, aux


def _moe_sharded(params, cfg: ModelConfig, x, rules, cf):
    """Expert-parallel MoE via nested shard_map (the production path).

    Tokens shard (batch over the data axes, sequence over 'model'); each
    rank dispatches its own tokens into an (E, C_loc, d) buffer; all_to_all
    moves expert rows to their owning rank for the grouped matmul and back.
    Dispatch buffers are per-rank sized (C_loc = T_loc*k*cf/E) — with the
    GSPMD-propagated global scatter they were the dominant memory term at
    train_4k (EXPERIMENTS.md §Perf iteration 2).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = rules.mesh
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    batch_ax = rules.rules.get("batch")
    model_ax = rules.rules.get("experts")
    xn = rmsnorm(params["ln"], x, cfg.norm_eps)

    m_size = rules.axis_size(model_ax)
    b_size = rules.axis_size(batch_ax)
    T_loc = (b // b_size) * (s // m_size)
    C_loc = max(1, int(T_loc * k * cf / E))
    batch_axes = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)

    # ZeRO-3 for expert weights *inside* the shard_map: weights enter
    # sharded on (experts x fsdp) and are all-gathered over the fsdp axis
    # just-in-time; autodiff turns the gather into a reduce-scatter, so the
    # expert grads leave 2-D sharded instead of transiently materializing
    # model-sharded-only f32 tensors (§Perf iteration 9 — arctic train).
    fsdp_ax = rules.rules.get("fsdp")
    use_fsdp = (isinstance(fsdp_ax, str) and fsdp_ax != model_ax
                and d % rules.axis_size(fsdp_ax) == 0)
    w_spec = P(model_ax, fsdp_ax, None) if use_fsdp \
        else P(model_ax, None, None)

    def inner(xs, router, we_g, we_u, we_d):
        bl, sl, _ = xs.shape
        if use_fsdp:
            we_g = jax.lax.all_gather(we_g, fsdp_ax, axis=1, tiled=True)
            we_u = jax.lax.all_gather(we_u, fsdp_ax, axis=1, tiled=True)
            # we_down's fsdp dim is d (last): gather along axis 2
            we_d = jax.lax.all_gather(we_d, fsdp_ax, axis=2, tiled=True)
        y, aux = _moe_local_dispatch(xs.reshape(bl * sl, d), router, we_g,
                                     we_u, we_d, E, k, C_loc, axis=model_ax)
        aux = jax.lax.pmean(aux, batch_axes + (model_ax,))
        return y.reshape(bl, sl, d), aux

    wd_spec = P(model_ax, None, fsdp_ax) if use_fsdp \
        else P(model_ax, None, None)
    y, aux = shard_map(
        inner, mesh=mesh,
        in_specs=(P(batch_ax, model_ax, None), P(), w_spec, w_spec,
                  wd_spec),
        out_specs=(P(batch_ax, model_ax, None), P()),
        check_vma=False,
    )(xn, params["router"], params["we_gate"], params["we_up"],
      params["we_down"])
    return constrain(y.astype(x.dtype), ("batch", "residual_seq", "d_model")), aux


def moe(params, cfg: ModelConfig, x, *, capacity_factor=None):
    """Top-k routed MoE with per-expert capacity (tokens over capacity drop).

    Dispatches to the expert-parallel shard_map path when a mesh is active
    and shapes divide; falls back to the single-device formulation (tests,
    decode, CPU examples) otherwise.
    """
    from repro.sharding.rules import active_rules

    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    cf = capacity_factor or cfg.capacity_factor

    rules = active_rules()
    if rules is not None:
        batch_ax = rules.rules.get("batch")
        model_ax = rules.rules.get("experts")
        m_size = rules.axis_size(model_ax)
        b_size = rules.axis_size(batch_ax)
        if (isinstance(model_ax, str) and m_size > 1 and E % m_size == 0
                and s % m_size == 0 and b % b_size == 0):
            return _moe_sharded(params, cfg, x, rules, cf)

    xn = rmsnorm(params["ln"], x, cfg.norm_eps)
    T = b * s
    C = max(1, int(T * k * cf / E))
    y, aux = _moe_local_dispatch(xn.reshape(T, d), params["router"],
                                 params["we_gate"], params["we_up"],
                                 params["we_down"], E, k, C)
    return constrain(y.reshape(b, s, d).astype(x.dtype),
                     ("batch", "residual_seq", "d_model")), aux


def moe_dense_reference(params, cfg: ModelConfig, x):
    """Every expert processes every token (oracle for tests; O(E) compute)."""
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    xn = rmsnorm(params["ln"], x, cfg.norm_eps)
    xt = xn.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xt, params["router"]).astype(jnp.float32)
    gate_all = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(gate_all, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    w = jnp.zeros((xt.shape[0], E), jnp.float32)
    w = w.at[jnp.arange(xt.shape[0])[:, None], eids].set(gates)
    h_g = jnp.einsum("td,edf->tef", xt, params["we_gate"])
    h_u = jnp.einsum("td,edf->tef", xt, params["we_up"])
    h = jax.nn.silu(h_g) * h_u
    out = jnp.einsum("tef,efd->ted", h, params["we_down"])
    y = jnp.einsum("ted,te->td", out.astype(jnp.float32), w)
    return y.reshape(b, s, d).astype(x.dtype)


def moe_load_balance_loss(gate_all, eids, E):
    """Switch-style auxiliary load-balancing loss."""
    T, k = eids.shape
    me = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(1.0) / (T * k)
    pe = gate_all.mean(axis=0)
    return E * jnp.sum(me * pe)


# ---------------------------------------------------------------------------
# Embeddings / logits
# ---------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig, dtype, vocab=None):
    v = vocab or cfg.vocab_size
    k1, k2 = jax.random.split(key)
    p = {"embed": dense_init(k1, (v, cfg.d_model), dtype, scale=0.02)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, (cfg.d_model, v), dtype,
                                  scale=1.0 / math.sqrt(cfg.d_model))
    return p


def embed(params, cfg: ModelConfig, tokens):
    y = jnp.take(params["embed"], tokens, axis=0)
    return constrain(y, ("batch", "residual_seq", "d_model"))


def logits(params, cfg: ModelConfig, x):
    w = params.get("unembed")
    if w is None:
        w = params["embed"].T
    y = jnp.einsum("bsd,dv->bsv", x, w)
    v = y.shape[-1]
    if cfg.vocab_real and cfg.vocab_real < v:
        # vocab was padded for sharding divisibility: mask padded entries
        mask = jnp.arange(v) < cfg.vocab_real
        y = jnp.where(mask, y, -1e30)
    return constrain(y, ("batch", "seq", "vocab"))
