"""Shared neural layers: norms, RoPE, chunked flash-style attention (train /
prefill / decode), dense MLPs, MoE with scatter-based dispatch, Mamba2 SSD.

All layers are pure functions over ParamSpec-declared pytrees; activations
carry logical sharding annotations via runtime.sharding.shard().
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.runtime.sharding import ParamSpec, shard

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_specs(cfg: ModelConfig, d: int) -> Dict:
    if cfg.norm == "layernorm":
        return {
            "scale": ParamSpec((d,), (None,), jnp.float32, "ones"),
            "bias": ParamSpec((d,), (None,), jnp.float32, "zeros"),
        }
    return {"scale": ParamSpec((d,), (None,), jnp.float32, "ones")}


def apply_norm(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]
    else:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"]
    return out.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap and cap > 0:
        return jnp.tanh(x / cap) * cap
    return x


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd), positions: (B, S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig) -> Dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    p = {
        "wq": ParamSpec((d, H * hd), ("fsdp", "qkv")),
        "wk": ParamSpec((d, KV * hd), ("fsdp", "qkv")),
        "wv": ParamSpec((d, KV * hd), ("fsdp", "qkv")),
        "wo": ParamSpec((H * hd, d), ("qkv", "fsdp")),
    }
    if cfg.qk_norm:
        p["qnorm"] = {"scale": ParamSpec((hd,), (None,), jnp.float32, "ones")}
        p["knorm"] = {"scale": ParamSpec((hd,), (None,), jnp.float32, "ones")}
    return p


def _qk_normalize(p, q, k, cfg):
    if not cfg.qk_norm:
        return q, k
    def rn(scale, x):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)
    return rn(p["qnorm"]["scale"], q), rn(p["knorm"]["scale"], k)


def _chunk_mask(qpos, kpos, layer_type: str, window: int, causal: bool):
    """(Sq, Sk) boolean mask given absolute positions."""
    diff = qpos[:, None] - kpos[None, :]
    m = kpos[None, :] < 2**29  # padded / unwritten cache slots are invalid
    if causal:
        m &= diff >= 0
    if layer_type == "local":
        m &= diff < window
    return m


def multihead_attention(
    x_q: jnp.ndarray,       # (B, Sq, H, hd) post-rope
    k: jnp.ndarray,         # (B, Sk, KV, hd)
    v: jnp.ndarray,         # (B, Sk, KV, hd)
    qpos: jnp.ndarray,      # (B, Sq)
    kpos: jnp.ndarray,      # (B, Sk)
    *,
    layer_type: str = "global",
    window: int = 0,
    causal: bool = True,
    attn_softcap: float = 0.0,
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    """Online-softmax (flash-style) chunked attention over key blocks.

    Memory never materialises (Sq, Sk) scores — peak is (B,H,Sq,kv_chunk).
    GQA is handled by reshaping q into (KV, group) without repeating K/V.
    """
    B, Sq, H, hd = x_q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    q = x_q.reshape(B, Sq, KV, G, hd) * (hd ** -0.5)

    if Sq <= 8:
        # decode: direct split-K attention.  The chunk SCAN below is
        # sequential, which forces GSPMD to all-gather a seq-sharded KV
        # cache (2 x full-cache per layer — perf iter Z1); the direct
        # einsum + sharded softmax lowers to tiny partial-max/sum
        # collectives instead.
        s = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
        s = softcap(s, attn_softcap)
        mask = jax.vmap(
            lambda qp, kp: _chunk_mask(qp, kp, layer_type, window, causal)
        )(qpos, kpos)  # (B, Sq, Sk)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        pexp = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m))
        l = jnp.sum(pexp, axis=-1, keepdims=True)
        out = jnp.einsum("bkgqs,bskh->bkgqh", (pexp / jnp.maximum(l, 1e-30)).astype(v.dtype), v)
        return jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd)

    nchunks = -(-Sk // kv_chunk)
    pad = nchunks * kv_chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=2**30)
    kc = k.reshape(B, nchunks, kv_chunk, KV, hd)
    vc = v.reshape(B, nchunks, kv_chunk, KV, hd)
    pc = kpos.reshape(B, nchunks, kv_chunk)

    def step(carry, blk):
        m_run, l_run, acc = carry
        kb, vb, pb = blk  # (B, C, KV, hd), (B, C, KV, hd), (B, C)
        s = jnp.einsum("bqkgh,bckh->bkgqc", q, kb).astype(jnp.float32)
        s = softcap(s, attn_softcap)
        mask = jax.vmap(
            lambda qp, kp: _chunk_mask(qp, kp, layer_type, window, causal)
        )(qpos, pb)  # (B, Sq, C)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        # fully-masked chunks: exp(NEG_INF - NEG_INF) would be 1 — force 0
        pexp = jnp.where(
            s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_new[..., None])
        )
        l_new = l_run * alpha + jnp.sum(pexp, axis=-1)
        upd = jnp.einsum("bkgqc,bckh->bkgqh", pexp.astype(vb.dtype), vb)
        acc = acc * alpha[..., None].astype(acc.dtype) + upd
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), v.dtype)
    (m, l, acc), _ = lax.scan(
        step,
        (m0, l0, a0),
        (
            jnp.moveaxis(kc, 1, 0),
            jnp.moveaxis(vc, 1, 0),
            jnp.moveaxis(pc, 1, 0),
        ),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd)  # (B,Sq,KV,G,hd)->(B,Sq,H*hd)
    return out


def attention_block(
    p: Dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    *,
    layer_type: str = "global",
    causal: bool = True,
    cache: Optional[Dict] = None,
    xattn_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full attention sublayer: projections + rope + (cached) attention.

    cache: {"k": (B,T,KV,hd), "v": ..., "len": ()} for decode; updated copy
    returned.  xattn_kv: precomputed (k, v, kpos) for cross-attention.
    """
    B, S, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    if xattn_kv is None:
        k = (x @ p["wk"]).reshape(B, S, KV, hd)
        v = (x @ p["wv"]).reshape(B, S, KV, hd)
        q, k = _qk_normalize(p, q, k, cfg)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        kpos = positions
    else:
        k, v, kpos = xattn_kv
        q, _ = _qk_normalize(p, q, q, cfg) if cfg.qk_norm else (q, None)
    # explicit attention layouts — the head/seq mode is chosen per arch by
    # launch.steps.rules_for (q_seq/kv_seq stay None in pure head-TP mode)
    q = shard(q, "batch", "q_seq", "heads", None)
    k = shard(k, "batch", "kv_seq", "kv_heads", None)
    v = shard(v, "batch", "kv_seq", "kv_heads", None)

    new_cache = None
    if cache is not None and xattn_kv is None:
        T = cache["k"].shape[1]
        start = cache["len"]
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0))
        new_cache = {"k": ck, "v": cv, "len": start + S}
        k, v = ck, cv
        kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        # mask out unwritten slots via "future" positions
        kpos = jnp.where(kpos < start + S, kpos, 2**30)

    out = multihead_attention(
        q, k, v, positions, kpos,
        layer_type=layer_type,
        window=cfg.window_size,
        causal=causal,
        attn_softcap=cfg.attn_softcap,
    )
    out = shard(out, "batch", "q_seq", "heads", None)
    out = out.reshape(B, S, H * hd) @ p["wo"]
    return shard(out, "batch", "residual_seq", None), new_cache


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, d: int, ff: int) -> Dict:
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "w_gate": ParamSpec((d, ff), ("fsdp", "ffn")),
            "w_in": ParamSpec((d, ff), ("fsdp", "ffn")),
            "w_out": ParamSpec((ff, d), ("ffn", "fsdp")),
        }
    return {
        "w_in": ParamSpec((d, ff), ("fsdp", "ffn")),
        "w_out": ParamSpec((ff, d), ("ffn", "fsdp")),
    }


def apply_mlp(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.mlp_act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_in"])
    elif cfg.mlp_act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_in"])
    else:
        h = jax.nn.gelu(x @ p["w_in"])
    h = shard(h, "batch", "seq", "ffn")
    return shard(h @ p["w_out"], "batch", "residual_seq", None)


# ---------------------------------------------------------------------------
# MoE (scatter dispatch into (E, C, d) bins + batched expert GEMMs)
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig) -> Dict:
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    p = {
        "router": ParamSpec((d, E), ("fsdp", None), jnp.float32),
        "w_gate": ParamSpec((E, d, ff), ("experts", "fsdp", "expert_ffn")),
        "w_in": ParamSpec((E, d, ff), ("experts", "fsdp", "expert_ffn")),
        "w_out": ParamSpec((E, ff, d), ("experts", "expert_ffn", "fsdp")),
    }
    if cfg.shared_experts:
        p["shared"] = mlp_specs(cfg, d, cfg.expert_d_ff * cfg.shared_experts)
    return p


def apply_moe(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k token-choice MoE.  Returns (out, aux_loss).

    Under a mesh with a 'model' axis this routes through the shard_map
    implementation (`_apply_moe_shardmap`): GSPMD partitions the scatter-
    based dispatch catastrophically (it all-reduces the full (E, C, d) bins
    per layer — 1 TB+/layer on kimi-k2; see EXPERIMENTS.md §Perf iter K1),
    whereas the explicit formulation keeps routing local and needs ONE psum.
    """
    from repro.runtime.sharding import current_mesh, _CTX

    mesh = current_mesh()
    if mesh is not None and "model" in mesh.shape:
        rules = _CTX.rules
        tp = mesh.shape["model"]
        if rules.get("experts") == "model" and cfg.num_experts % tp == 0:
            batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
            bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
            if batch_axes and x.shape[0] % bsz == 0:
                # seq-sharded residual -> expert-parallel all-to-all island
                # (perf iter K4); else replicated-token island (decode)
                if rules.get("residual_seq") == "model" and x.shape[1] % tp == 0:
                    return _apply_moe_ep_a2a(p, x, cfg, mesh, batch_axes)
                return _apply_moe_shardmap(p, x, cfg, mesh, batch_axes)
    return _apply_moe_dense(p, x, cfg)


def _apply_moe_ep_a2a(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, mesh, batch_axes
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE with explicit all-to-all dispatch (GShard-style).

    Tokens are sharded over BOTH batch axes and the model axis (seq); each
    shard routes its local tokens, scatters them into per-expert send
    buffers, exchanges with the expert owners by all_to_all, runs the expert
    GEMMs, and reverses the exchange.  Per layer collective cost is exactly
    2 x T_local*k*cf*d (fwd) — no replicated bins, no full-activation psum.
    """
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_tok
    tp = mesh.shape["model"]
    El = E // tp
    nmat_glu = cfg.mlp_act in ("swiglu", "geglu")
    act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu

    def body(xl, router, wg, wi, wo, *shared):
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, d)
        logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        me = lax.pmean(jnp.mean(probs, axis=0), batch_axes + ("model",))
        ce = lax.pmean(
            jnp.mean(
                jnp.sum(jax.nn.one_hot(expert_ids, E, dtype=jnp.float32), axis=1),
                axis=0,
            ),
            batch_axes + ("model",),
        )
        aux = jnp.sum(me * ce) * E

        flat_e = expert_ids.reshape(T * K).astype(jnp.int32)
        pos = _local_positions(flat_e, E)
        Cs = max(1, int(T * K * cfg.capacity_factor / E))  # per-source capacity
        ok = pos < Cs
        dst = jnp.where(ok, flat_e * Cs + pos, E * Cs)
        src = jnp.repeat(xt, K, axis=0)
        send = jnp.zeros((E * Cs + 1, d), xt.dtype).at[dst].add(src)
        send = send[: E * Cs].reshape(tp, El * Cs, d)
        recv = lax.all_to_all(send, "model", split_axis=0, concat_axis=0)
        bins = recv.reshape(tp, El, Cs, d).transpose(1, 0, 2, 3).reshape(
            El, tp * Cs, d
        )
        if nmat_glu:
            h = act(jnp.einsum("ecd,edf->ecf", bins, wg)) * jnp.einsum(
                "ecd,edf->ecf", bins, wi
            )
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", bins, wi))
        outb = jnp.einsum("ecf,efd->ecd", h, wo)  # (El, tp*Cs, d)
        back = outb.reshape(El, tp, Cs, d).transpose(1, 0, 2, 3).reshape(
            tp, El * Cs, d
        )
        ret = lax.all_to_all(back, "model", split_axis=0, concat_axis=0)
        ret = ret.reshape(E * Cs, d)
        ret = jnp.concatenate([ret, jnp.zeros((1, d), ret.dtype)])
        gathered = jnp.take(ret, dst, axis=0)
        weighted = gathered.reshape(T, K, d) * gate_vals[..., None].astype(
            gathered.dtype
        )
        out = jnp.sum(weighted, axis=1)  # (T, d) — already complete locally
        if shared:
            # tokens are seq-sharded here, so the (small) shared-expert
            # weights are REPLICATED over model: every rank serves its own
            # tokens completely — a psum of partial-f products would mix
            # different ranks' tokens (bug caught by the parity test)
            sg, si, so = shared
            if nmat_glu:
                hs = act(xt @ sg) * (xt @ si)
            else:
                hs = jax.nn.gelu(xt @ si)
            out = out + hs @ so
        return out.reshape(Bl, Sl, d), aux

    bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], "model", None)
    espec = P("model", None, None)
    if cfg.shared_experts:
        sp = p["shared"]
        shared = (
            sp["w_gate"] if "w_gate" in sp else sp["w_in"],
            sp["w_in"],
            sp["w_out"],
        )
        sspec = (P(None, None), P(None, None), P(None, None))  # replicated
    else:
        shared = ()
        sspec = ()
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(bspec, P(None, None), espec, espec, espec) + sspec,
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["router"].astype(jnp.float32), p["w_gate"], p["w_in"], p["w_out"], *shared)
    return out, aux


def _local_positions(flat_e: jnp.ndarray, E: int) -> jnp.ndarray:
    """Rank of each routing decision within its expert (sort-based, local).

    Avoids the (T*K, E) one-hot cumsum tensor entirely."""
    TK = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = jnp.take(flat_e, order)
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(TK, dtype=jnp.int32) - first.astype(jnp.int32)
    pos = jnp.zeros((TK,), jnp.int32).at[order].set(rank)
    return pos


def _apply_moe_shardmap(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, mesh, batch_axes
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit MoE: tokens sharded over batch axes and REPLICATED over
    'model'; each model rank routes the local tokens to its own expert slab;
    a single psum over 'model' combines expert (and shared-FFN) partials."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_tok
    tp = mesh.shape["model"]
    El = E // tp
    nmat_glu = cfg.mlp_act in ("swiglu", "geglu")
    act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu

    def body(xl, router, wg, wi, wo, shared):
        m = lax.axis_index("model")
        Bl = xl.shape[0]
        T = Bl * S
        xt = xl.reshape(T, d)
        logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        # load-balance aux (global over batch axes; replicated over model)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            jnp.sum(jax.nn.one_hot(expert_ids, E, dtype=jnp.float32), axis=1), axis=0
        )
        if batch_axes:
            me = lax.pmean(me, batch_axes)
            ce = lax.pmean(ce, batch_axes)
        aux = jnp.sum(me * ce) * E

        flat_e = expert_ids.reshape(T * K).astype(jnp.int32)
        pos = _local_positions(flat_e, E)
        C = max(1, int(T * K * cfg.capacity_factor / E))
        local_e = flat_e - m * El
        ok = (local_e >= 0) & (local_e < El) & (pos < C)
        dst = jnp.where(ok, jnp.clip(local_e, 0, El - 1) * C + pos, El * C)
        src = jnp.repeat(xt, K, axis=0)  # (T*K, d)
        bins = jnp.zeros((El * C + 1, d), xt.dtype).at[dst].add(src)
        bins = bins[: El * C].reshape(El, C, d)
        if nmat_glu:
            h = act(jnp.einsum("ecd,edf->ecf", bins, wg)) * jnp.einsum(
                "ecd,edf->ecf", bins, wi
            )
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", bins, wi))
        out_bins = jnp.einsum("ecf,efd->ecd", h, wo).reshape(El * C, d)
        out_bins = jnp.concatenate([out_bins, jnp.zeros((1, d), out_bins.dtype)])
        gathered = jnp.take(out_bins, dst, axis=0)  # masked rows hit the 0-row
        weighted = gathered.reshape(T, K, d) * gate_vals[..., None].astype(
            gathered.dtype
        )
        partial = jnp.sum(weighted, axis=1)  # (T, d)
        if shared is not None:
            sg, si, so = shared
            if nmat_glu:
                hs = act(xt @ sg) * (xt @ si)
            else:
                hs = jax.nn.gelu(xt @ si)
            partial = partial + hs @ so
        out = lax.psum(partial.astype(xl.dtype), "model")  # bf16 payload
        return out.reshape(Bl, S, d), aux

    bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None)
    espec = P("model", None, None)
    if cfg.shared_experts:
        sp = p["shared"]
        shared = (
            sp["w_gate"] if "w_gate" in sp else sp["w_in"],
            sp["w_in"],
            sp["w_out"],
        )
        sspec = (P(None, "model"), P(None, "model"), P("model", None))
    else:
        shared = ()
        sspec = ()
    out, aux = jax.shard_map(
        lambda xl, router, wg, wi, wo, *sh: body(xl, router, wg, wi, wo, sh or None),
        mesh=mesh,
        in_specs=(bspec, P(None, None), espec, espec, espec) + sspec,
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["router"].astype(jnp.float32), p["w_gate"], p["w_in"], p["w_out"], *shared)
    return out, aux


def _apply_moe_dense(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference dense-dispatch MoE (single device / no mesh)."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_tok
    T = B * S
    xt = x.reshape(T, d)
    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, E, dtype=jnp.float32), axis=1), axis=0
    )
    aux = jnp.sum(me * ce) * E

    C = max(1, int(T * K * cfg.capacity_factor / E))
    flat_e = expert_ids.reshape(T * K)
    # position of each (token, k) within its expert bin
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*K, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1)
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]  # (T*K,)
    keep = pos < C
    safe_pos = jnp.where(keep, pos, C)  # overflow row
    # scatter tokens into bins (E, C+1, d); +1 row swallows dropped tokens
    src = jnp.repeat(xt, K, axis=0)  # (T*K, d)
    bins = jnp.zeros((E, C + 1, d), xt.dtype)
    bins = bins.at[flat_e, safe_pos].add(src)
    bins = shard(bins, "experts", None, None)
    # expert FFNs (batched GEMMs over E)
    if cfg.mlp_act in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", bins, p["w_gate"])) * jnp.einsum(
            "ecd,edf->ecf", bins, p["w_in"]
        )
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", bins, p["w_in"]))
    h = shard(h, "experts", None, None)
    out_bins = jnp.einsum("ecf,efd->ecd", h, p["w_out"])  # (E, C+1, d)
    # gather back
    gathered = out_bins[flat_e, safe_pos]  # (T*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    weighted = gathered.reshape(T, K, d) * gate_vals[..., None].astype(gathered.dtype)
    out = jnp.sum(weighted, axis=1).reshape(B, S, d)
    if cfg.shared_experts:
        out = out + apply_mlp(p["shared"], x, cfg)
    return shard(out, "batch", "seq", None), aux


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd_specs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    di = cfg.ssm_d_inner
    N = cfg.ssm_state
    H = di // cfg.ssm_head_dim
    conv_dim = di + 2 * N
    return {
        "in_proj": ParamSpec((d, 2 * di + 2 * N + H), ("fsdp", "ffn")),
        "conv_w": ParamSpec((cfg.ssm_conv, conv_dim), ("conv", None)),
        "A_log": ParamSpec((H,), (None,), jnp.float32, "zeros"),
        "D": ParamSpec((H,), (None,), jnp.float32, "ones"),
        "dt_bias": ParamSpec((H,), (None,), jnp.float32, "zeros"),
        "out_norm": {"scale": ParamSpec((di,), (None,), jnp.float32, "ones")},
        "out_proj": ParamSpec((di, d), ("ffn", "fsdp")),
    }


def _ssd_scan(x, dt, A, Bm, Cm, chunk: int, state0=None):
    """Chunked state-space dual scan.

    x: (B, L, H, P); dt: (B, L, H); A: (H,) (negative decay rates);
    Bm, Cm: (B, L, N).  Returns (y: (B, L, H, P), final_state (B,H,N,P)).
    """
    Bsz, L, H, Pd = x.shape
    N = Bm.shape[-1]
    nc = L // chunk
    assert nc * chunk == L, (L, chunk)
    xr = x.reshape(Bsz, nc, chunk, H, Pd)
    dtr = dt.reshape(Bsz, nc, chunk, H)
    Br = Bm.reshape(Bsz, nc, chunk, N)
    Cr = Cm.reshape(Bsz, nc, chunk, N)

    dA = dtr * A[None, None, None, :]  # (B, nc, c, H) negative values
    cum = jnp.cumsum(dA, axis=2)  # within-chunk cumulative log decay

    def step(state, blk):
        xb, dtb, Bb, Cb, dAb, cumb = blk  # (B,c,H,P),(B,c,H),(B,c,N),(B,c,N),(B,c,H),(B,c,H)
        # intra-chunk: y[i] = sum_{j<=i} C_i.B_j exp(cum_i - cum_j) dt_j x_j
        Lmat = cumb[:, :, None, :] - cumb[:, None, :, :]  # (B, i, j, H)
        causal = jnp.tril(jnp.ones((Lmat.shape[1], Lmat.shape[2]), bool))
        # mask in log space BEFORE exp: avoids inf (and nan grads) above diag
        decay = jnp.exp(jnp.where(causal[None, :, :, None], Lmat, NEG_INF))
        cb = jnp.einsum("bin,bjn->bij", Cb, Bb)  # (B, i, j)
        w = cb[..., None] * decay * dtb[:, None, :, :]  # (B, i, j, H)
        y_intra = jnp.einsum("bijh,bjhp->bihp", w.astype(xb.dtype), xb)
        # inter-chunk: y_i += C_i . state * exp(cum_i)
        y_inter = jnp.einsum(
            "bin,bhnp->bihp", Cb, state.astype(Cb.dtype)
        ) * jnp.exp(cumb)[..., None].astype(xb.dtype)
        # state update: S' = S * exp(sum dA) + sum_j exp(cum_end - cum_j) dt_j B_j x_j^T
        tail = jnp.exp(cumb[:, -1:, :] - cumb) * dtb  # (B, c, H)
        dBx = jnp.einsum("bjh,bjn,bjhp->bhnp", tail.astype(xb.dtype), Bb, xb)
        state = state * jnp.exp(cumb[:, -1])[:, :, None, None].astype(state.dtype) + dBx
        return state, y_intra + y_inter

    if state0 is None:
        state0 = jnp.zeros((Bsz, H, N, Pd), jnp.float32)
    state_f, ys = lax.scan(
        step,
        state0,
        (
            jnp.moveaxis(xr, 1, 0),
            jnp.moveaxis(dtr, 1, 0),
            jnp.moveaxis(Br, 1, 0),
            jnp.moveaxis(Cr, 1, 0),
            jnp.moveaxis(dA, 1, 0),
            jnp.moveaxis(cum, 1, 0),
        ),
    )
    return jnp.moveaxis(ys, 0, 1).reshape(Bsz, L, H, Pd), state_f


def apply_ssd(
    p: Dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    cache: Optional[Dict] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Mamba2 block.  cache = {"conv": (B, K-1, convdim), "state": (B,H,N,P)}."""
    B, S, d = x.shape
    di, N = cfg.ssm_d_inner, cfg.ssm_state
    H = di // cfg.ssm_head_dim
    Pd = cfg.ssm_head_dim
    proj = x @ p["in_proj"]  # (B, S, 2di + 2N + H)
    z, xbc, dt_raw = jnp.split(proj, [di, 2 * di + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])  # (H,) negative

    # causal depthwise conv over xbc
    Kc = cfg.ssm_conv
    new_cache = None
    if cache is None:
        pad = jnp.pad(xbc, ((0, 0), (Kc - 1, 0), (0, 0)))
        conv = sum(
            pad[:, i : i + S] * p["conv_w"][i][None, None].astype(x.dtype)
            for i in range(Kc)
        )
    else:
        hist = jnp.concatenate([cache["conv"], xbc], axis=1)  # (B, K-1+S, cd)
        conv = sum(
            hist[:, i : i + S] * p["conv_w"][i][None, None].astype(x.dtype)
            for i in range(Kc)
        )
        new_conv = hist[:, -(Kc - 1):]
    conv = jax.nn.silu(conv)
    xs, Bm, Cm = jnp.split(conv, [di, di + N], axis=-1)
    xh = xs.reshape(B, S, H, Pd)
    xh = shard(xh, "batch", "seq", "ssm_heads", None)

    if cache is None:
        L = xh.shape[1]
        chunk = min(cfg.ssm_chunk, L)
        while L % chunk:
            chunk //= 2
        y, _ = _ssd_scan(xh, dt, A, Bm, Cm, max(chunk, 1))
    elif S > 1:
        # prefill continuing from a cached state (SSM "cache" = final state)
        L = xh.shape[1]
        chunk = min(cfg.ssm_chunk, L)
        while L % chunk:
            chunk //= 2
        y, state = _ssd_scan(xh, dt, A, Bm, Cm, max(chunk, 1), state0=cache["state"])
        new_cache = {"conv": new_conv, "state": state}
    else:
        # single-step recurrence (S == 1 decode)
        state = cache["state"]  # (B, H, N, P) float32
        dA1 = jnp.exp(dt[:, 0] * A[None, :])  # (B, H)
        dBx = jnp.einsum(
            "bh,bn,bhp->bhnp", dt[:, 0], Bm[:, 0].astype(jnp.float32),
            xh[:, 0].astype(jnp.float32),
        )
        state = state * dA1[:, :, None, None] + dBx
        y = jnp.einsum("bn,bhnp->bhp", Cm[:, 0].astype(jnp.float32), state)[
            :, None
        ].astype(x.dtype)
        new_cache = {"conv": new_conv, "state": state}
    y = y + xh * p["D"][None, None, :, None].astype(x.dtype)
    y = y.reshape(B, S, di)
    # gated RMS norm then out projection
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    yf = yf * jax.lax.rsqrt(var + 1e-6) * p["out_norm"]["scale"]
    out = yf.astype(x.dtype) @ p["out_proj"]
    return shard(out, "batch", "seq", None), new_cache
