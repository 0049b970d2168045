"""Plain reference of the gated detector: Moonlight-16B-A3B over frame patches.

Moonlight-16B-A3B (``deepseek_v3``; https://huggingface.co/moonshotai/
Moonlight-16B-A3B/blob/main/config.json), as one chip of an 8-way
expert-parallel deployment serves it in the cascade:

- each ``H x W`` high-precision frame is cut into ``patch x patch``
  patches, projected to ``d_model`` and given a learned position
  embedding (this stands in for the token embedding);
- 27 pre-norm decoder blocks (RMS norm, eps 1e-5), causal: multi-head
  latent attention, residual; then a SwiGLU feed-forward, residual. Block
  0's feed-forward is dense (11,264 wide). Blocks 1-26 route each token
  over all 64 experts: sigmoid scores of float32 router logits, the top 6
  chosen by score plus the correction bias, weighted by score alone,
  renormalised over the 6 and scaled by 2.446. Only the experts this chip
  holds (``expert_offset`` .. ``+ n_experts``) give their part, each on
  every token and weighted by that token's routing weight for it (zero
  where it was not chosen); the part of the absent experts is left out.
  Two shared experts, one SwiGLU 2,816 wide, run on every token;
- latent attention: queries from one projection, per head 128 plain and
  64 rotary dims; a 512-dim latent (RMS-normed, eps 1e-6 as the published
  code leaves it) expanded to per-head 128-dim key and value parts; one
  64-dim rotary key shared by the heads; rotary embedding at theta 50,000
  on the dims as interleaved pairs (the published layout); scores scaled
  by 192^-1/2;
- a final RMS norm and the untied head; the detector reads the first
  ``n_out`` outputs at the last position.

Every product runs in the compute dtype the configuration states:
bfloat16 operands, float32 accumulation; norm statistics, softmax, the
rotary arithmetic and the expert combination stay in float32, and the
routing is float32 in every mode. Two more modes (:data:`MODES`) serve
the check: ``float32`` throughout, and ``float8``, which rounds every
product's operands to float8 (e4m3) first: the control, one precision
below the stated one.

The weights are made here from the seed, on the device, in one call, in
the configuration's parameter dtype (the embedder in float32), in the
tree layout the cascade takes. The module keeps the detector references'
contract (``bench/reference/__init__.py``), and counts the work of the
held experts' grouped matmul for their roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn

#: standard deviation of the random router correction bias
BIAS_SIGMA = 0.01
#: the latent's RMS-norm eps (the published code leaves the default)
LATENT_EPS = 1e-6


def tokens(g: dict, d: dict) -> int:
    return (g["frame_h"] // d["patch"]) * (g["frame_w"] // d["patch"])


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _zero_sums(proj):
    """The patch projection with each output's weights summing to zero.

    Every patch of a radar frame shares the speckle floor. A random
    projection maps that common level to one direction in every token,
    and random routers then send most tokens of a layer to the same few
    experts. Without the patch's mean level the routers spread the tokens
    over the experts, and the correction bias stays random, as no training
    has set it (PERF.md, Findings)."""
    return proj - proj.mean(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("shapes",))
def _init(key, *, shapes):
    """Weights from ``key``: fan-in scaled normals (the patch projection
    with zero sums, :func:`_zero_sums`), unit norms."""
    (L, dm, nh, r, nope, rope, vd, f, fd, e, E, sh, vocab, pp, seq,
     pdt) = shapes
    dt = _dtype(pdt)
    keys = iter(jax.random.split(key, 32))

    def nrm(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dt)

    def attn(*lead):
        return {"wq": nrm((*lead, dm, nh, nope + rope), dm),
                "wkv_a": nrm((*lead, dm, r + rope), dm),
                "kv_norm": {"scale": ones(*lead, r)},
                "wkv_b": nrm((*lead, r, nh, nope + vd), r),
                "wo": nrm((*lead, nh, vd, dm), nh * vd)}

    def swiglu(width, *lead):
        return {"w_gate": nrm((*lead, dm, width), dm),
                "w_up": nrm((*lead, dm, width), dm),
                "w_down": nrm((*lead, width, dm), width)}

    n = L - 1
    return {
        "backbone": {
            "final_norm": {"scale": ones(dm)},
            "unembed": {"kernel": nrm((dm, vocab), dm)},
            "dense_layers": [{"attn_norm": {"scale": ones(dm)},
                              "attn": attn(),
                              "mlp_norm": {"scale": ones(dm)},
                              "mlp": swiglu(fd)}],
            "layers": {
                "attn_norm": {"scale": ones(n, dm)},
                "attn": attn(n),
                "mlp_norm": {"scale": ones(n, dm)},
                "experts": {
                    "router": nrm((n, dm, E), dm),
                    "router_bias": (BIAS_SIGMA * jax.random.normal(
                        next(keys), (n, E), jnp.float32)).astype(dt),
                    **swiglu(f, n, e),
                    "shared": swiglu(sh * f, n),
                },
            },
        },
        "embedder": {
            "proj": _zero_sums(jax.random.normal(next(keys), (pp, dm),
                                                 jnp.float32)
                               / float(np.sqrt(pp))),
            "pos": 0.02 * jax.random.normal(next(keys), (seq, dm),
                                            jnp.float32),
        },
    }


def make_weights(key, g: dict, d: dict) -> dict:
    if d["first_dense_layers"] != 1:
        raise ValueError("the reference holds one leading dense layer")
    shapes = (d["n_layers"], d["d_model"], d["n_heads"], d["kv_lora_rank"],
              d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"],
              d["d_ff"], d["dense_d_ff"], d["n_experts"], d["router_experts"],
              d["n_shared_experts"], d["vocab"], d["patch"] * d["patch"],
              tokens(g, d), d["param_dtype"])
    return _init(key, shapes=shapes)


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------

def _attn_flops(s: int, d: dict) -> float:
    """Per token: the query, latent, expansion and output projections, and
    scores and weighted sum over all ``s`` tokens."""
    dm, h, r = d["d_model"], d["n_heads"], d["kv_lora_rank"]
    qk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    vd = d["v_head_dim"]
    proj = (dm * h * qk + dm * (r + d["qk_rope_head_dim"])
            + r * h * (d["qk_nope_head_dim"] + vd) + h * vd * dm)
    return 2.0 * proj + 2.0 * s * h * (qk + vd)


def expert_pair_flops(d: dict) -> float:
    """FLOPs of one (token, expert) pair through a SwiGLU expert: the gate,
    up and down projections."""
    return 3 * 2.0 * d["d_model"] * d["d_ff"]


def expert_pair_bytes(d: dict) -> float:
    """Least bytes a pair moves in the grouped matmul: its token's row in
    and its output row out, in the compute dtype."""
    return 2 * d["d_model"] * jnp.dtype(_dtype(d["compute_dtype"])).itemsize


def expert_layer_bytes(d: dict) -> float:
    """Least bytes of one expert layer's grouped matmul besides its pairs:
    the held experts' weights, read once."""
    return (3 * d["n_experts"] * d["d_model"] * d["d_ff"]
            * jnp.dtype(_dtype(d["param_dtype"])).itemsize)


def frame_flops(g: dict, d: dict) -> float:
    """Forward FLOPs of one real frame.

    Per token, every layer's attention (:func:`_attn_flops`: 30.1 MFLOP at
    the published sizes and 256 tokens); the dense layer's SwiGLU
    ``6 d dense_d_ff``; each expert layer's router ``2 d E``, shared
    SwiGLU ``6 d (shared x d_ff)`` and its held experts' expected pairs,
    ``top_k x held / E`` of them at :func:`expert_pair_flops` each. Then
    the head at the last position, ``2 d vocab``. For 128x128 frames in
    8x8 patches (256 tokens), 1 dense and 26 expert layers with 8 of 64
    experts held: about 0.5629 TFLOP.
    """
    s = tokens(g, d)
    dm = d["d_model"]
    n_moe = d["n_layers"] - d["first_dense_layers"]
    attn = _attn_flops(s, d)
    dense = attn + 6.0 * dm * d["dense_d_ff"]
    moe = (attn + 2.0 * dm * d["router_experts"]
           + 6.0 * dm * d["n_shared_experts"] * d["d_ff"]
           + d["top_k"] * d["n_experts"] / d["router_experts"]
           * expert_pair_flops(d))
    per_token = d["first_dense_layers"] * dense + n_moe * moe
    return float(s * per_token + 2.0 * dm * d["vocab"])


def tiny(d: dict) -> dict:
    """The keys of ``d`` a CPU test shrinks: 1 dense and 2 expert layers of
    width 64, 4 of 16 routed experts held, top-3."""
    return {"n_layers": 3, "d_model": 64, "n_heads": 4, "kv_heads": 4,
            "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
            "v_head_dim": 8, "d_ff": 32, "dense_d_ff": 128, "n_experts": 4,
            "router_experts": 16, "top_k": 3, "vocab": 128, "batch": 4}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms(x, scale, eps, cd):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * scale.astype(jnp.float32)).astype(cd)


def _rope(x, theta):
    """Rotary embedding on ``(s, ..., n)``: dims ``2i`` and ``2i + 1`` are
    one pair, turned by ``position x theta^(-2i/n)``."""
    s, n = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # (s, n/2)
    ang = ang.reshape(s, *([1] * (x.ndim - 2)), n // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    re, im = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     axis=-1).reshape(x.shape)


def _route(x, ep, d):
    """Float32 routing of ``(s, dm)`` tokens: ``(s, E)`` weights, zero
    where an expert was not chosen."""
    logits = jnp.dot(x.astype(jnp.float32), ep["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + ep["router_bias"].astype(jnp.float32),
                           d["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * d["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)


def _one_frame(params, frame, *, d, cd, q8):
    H, W = frame.shape
    patch, n_out = d["patch"], d["n_out"]
    seq = (H // patch) * (W // patch)
    nope, r = d["qk_nope_head_dim"], d["kv_lora_rank"]
    eps, theta = d["norm_eps"], float(d["rope_theta"])

    def mm(spec, a, b):
        """A product on ``cd`` operands (float8-rounded under ``q8``),
        accumulated in float32."""
        a, b = a.astype(cd), b.astype(cd)
        if q8:
            a, b = a.astype(F8).astype(cd), b.astype(F8).astype(cd)
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    def swiglu(x, p):
        g = mm("sd,df->sf", x, p["w_gate"])
        u = mm("sd,df->sf", x, p["w_up"])
        return mm("sf,fd->sd", (jax.nn.silu(g) * u).astype(cd), p["w_down"])

    def attend(x, p):
        q = mm("sd,dhk->shk", x, p["wq"])
        ckv = mm("sd,dk->sk", x, p["wkv_a"])
        kv = mm("sr,rhk->shk", _rms(ckv[:, :r], p["kv_norm"]["scale"],
                                    LATENT_EPS, cd), p["wkv_b"])
        q_pe = _rope(q[:, :, nope:], theta)
        k_pe = _rope(ckv[:, r:], theta)                         # (s, rope)
        s = (mm("qhk,shk->hqs", q[:, :, :nope], kv[:, :, :nope])
             + mm("qhk,sk->hqs", q_pe, k_pe))
        s = s / np.float32(np.sqrt(q.shape[-1]))
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        s = jnp.where(causal[None], s, jnp.finfo(jnp.float32).min)
        o = mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), kv[:, :, nope:])
        return mm("shk,hkd->sd", o, p["wo"]).astype(cd)

    def block(x, lp, ffn):
        x = x + attend(_rms(x, lp["attn_norm"]["scale"], eps, cd), lp["attn"])
        return x + ffn(_rms(x, lp["mlp_norm"]["scale"], eps, cd)).astype(cd)

    def experts(x, ep):
        w = _route(x, ep, d)
        lo = d["expert_offset"]
        y = jnp.zeros((x.shape[0], d["d_model"]), jnp.float32)
        for j in range(d["n_experts"]):
            part = {k: ep[k][j] for k in ("w_gate", "w_up", "w_down")}
            y = y + w[:, lo + j, None] * swiglu(x, part).astype(cd)
        return y.astype(cd) + swiglu(x, ep["shared"]).astype(cd)

    p = frame.reshape(H // patch, patch, W // patch, patch)
    p = p.transpose(0, 2, 1, 3).reshape(seq, patch * patch)
    x = (mm("sp,pd->sd", p, params["embedder"]["proj"])
         + params["embedder"]["pos"]).astype(cd)
    bb = params["backbone"]
    for lp in bb["dense_layers"]:
        x = block(x, lp, lambda m: swiglu(m, lp["mlp"]))
    x, _ = jax.lax.scan(
        lambda x, lp: (block(x, lp, lambda m: experts(m, lp["experts"])),
                       None), x, bb["layers"])
    x = _rms(x[-1:], bb["final_norm"]["scale"], eps, cd)
    return mm("sd,dv->sv", x, bb["unembed"]["kernel"][:, :n_out])[0]


#: the reference's modes: the configuration's bfloat16; float32 (how much
#: the network itself moves under bfloat16 rounding); the float8 control
MODES = {"bfloat16": (jnp.bfloat16, False), "float32": (jnp.float32, False),
         "float8": (jnp.bfloat16, True)}

#: the detector keys the forward reads
_KEYS = ("patch", "n_out", "qk_nope_head_dim", "kv_lora_rank", "norm_eps",
         "rope_theta", "top_k", "routed_scaling_factor", "expert_offset",
         "n_experts", "d_model")


@functools.partial(jax.jit, static_argnames=("dk", "mode"))
def _forward(params, frames, *, dk, mode):
    cd, q8 = MODES[mode]
    f = functools.partial(_one_frame, d=dict(dk), cd=cd, q8=q8)
    return jax.vmap(lambda fr: f(params, fr))(frames)


def logits(params, frames: np.ndarray, d: dict, *, mode: str = "bfloat16",
           block: int = 8) -> np.ndarray:
    """``(M, H, W)`` high-precision frames -> ``(M, n_out)`` logits in
    ``mode`` (:data:`MODES`), ``block`` frames per call (the last block
    padded)."""
    frames = np.asarray(frames, np.float32)
    dk = tuple((k, d[k]) for k in _KEYS)
    out = np.empty((frames.shape[0], d["n_out"]), np.float32)
    for lo in range(0, frames.shape[0], block):
        part = frames[lo:lo + block]
        m = part.shape[0]
        if m < block:
            part = np.concatenate(
                [part, np.zeros((block - m, *part.shape[1:]), np.float32)])
        r = _forward(params, jnp.asarray(part), dk=dk, mode=mode)
        out[lo:lo + m] = np.asarray(r)[:m]
    return out
