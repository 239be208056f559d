"""The hybrid block of the ``granitemoehybrid`` configurations (Granite 4.0-H):
how a configuration file maps onto the program, the weights' layout, the
plain reference and the model FLOPs.

Each layer is a Mamba-2 mixer or a NoPE grouped-query attention, as the
file's ``layer_types`` says, each followed by a shared SwiGLU MLP; RMSNorm
before each sub-block, whose output is scaled by ``residual_multiplier``
before it is added; the embedding scaled by ``embedding_multiplier``, tied
to the output head, whose logits are divided by ``logits_scaling``.

The reference is written in ``jax.numpy`` in float32 with every matrix
product at ``Precision.HIGHEST``.  Its Mamba-2 mixer is the sequential
recurrence, one step at a time,

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,   y_t = C_t h_t + D x_t,

in ``jax.checkpoint``ed blocks of steps, and not the program's chunked form;
the gate comes before the RMS norm over each group's width.  ``quant=True``
rounds every product's operands to float8 e4m3 (``reference.fp8``), the
control.  Nothing here imports the program but ``program_config``.

A configuration may be one chip's share of a tensor-parallel deployment
(``tensor_parallel``): the file then states the heads, the vocabulary and
the MLP columns held here, and the reference computes what this chip
computes, without the exchange: partial sums where the deployment would
all-reduce them, and the gated norm's variance over the channels held here.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import NEG, _mm, _rmsnorm, fp8

#: ``jax.named_scope`` names of this block beyond the shared ones: the
#: Mamba-2 mixer, and inside it the chunked scan
SCOPES = ("mamba", "ssd")

#: steps of the sequential recurrence per ``jax.checkpoint``ed block
STEP_BLOCK = 64


def program_config(config):
    """The program's ``ModelConfig`` for a configuration file: the preset
    the file names, with every size and the layer pattern the file states,
    checked against the settings the file states and the program does not
    take as sizes."""
    from repro.configs import get_config
    base = get_config(config["program_arch"])
    tp = config.get("tensor_parallel", 1)
    d, di = config["hidden_size"], config["mamba_n_heads"] * config["mamba_d_head"]
    structure = {
        "hidden_act": (config["hidden_act"], "silu"),
        "normalization_function": (config["normalization_function"], "rmsnorm"),
        "mamba_proj_bias": (config["mamba_proj_bias"], False),
        "num_local_experts": (config["num_local_experts"], 0),
        "mamba inner width x tensor_parallel": (di * tp,
                                                config["mamba_expand"] * d),
        "MLP columns held x tensor_parallel": (
            config["shared_intermediate_held"] * tp,
            config["shared_intermediate_size"]),
    }
    bad = {k: v for k, v in structure.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"the program has no such {config['model_type']} "
                         f"block: {bad}")
    ssm = dataclasses.replace(
        base.ssm, d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        n_ssm_heads=config["mamba_n_heads"], head_dim=config["mamba_d_head"],
        chunk=config["mamba_chunk_size"], n_groups=config["mamba_n_groups"],
        conv_bias=config["mamba_conv_bias"])
    cfg = dataclasses.replace(
        base, n_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]), d_model=d,
        d_ff=config["shared_intermediate_held"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        vocab_size=config["vocab_size"], ssm=ssm)
    want = {"position_embedding": config["position_embedding_type"],
            "embedding_multiplier": config["embedding_multiplier"],
            "attention_multiplier": config["attention_multiplier"],
            "residual_multiplier": config["residual_multiplier"],
            "logits_scaling": config["logits_scaling"],
            "norm_eps": config["rms_norm_eps"],
            "tie_embeddings": config["tie_word_embeddings"],
            "qkv_bias": config["attention_bias"],
            "param_dtype": config["torch_dtype"],
            "compute_dtype": config["torch_dtype"],
            "opt_state_dtype": config["optimizer_state_dtype"],
            "padded_vocab": config["padded_vocab"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"the program's {cfg.name} differs from the "
                         f"configuration file: {bad}")
    return cfg


# ---------------------------------------------------------------------------
# the layer pattern and the weights: the program's parameter tree
# ---------------------------------------------------------------------------

def segments(config):
    """[(kind, n)] of the program's segments: each run of "mamba" layers is
    one stacked segment, each "attention" layer one of its own (n None)."""
    out = []
    for kind in config["layer_types"]:
        if kind == "mamba" and out and out[-1][0] == "mamba":
            out[-1] = ("mamba", out[-1][1] + 1)
        else:
            out.append((kind, 1 if kind == "mamba" else None))
    return out


def _sizes(config):
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    return {"d": config["hidden_size"], "f": config["shared_intermediate_held"],
            "H": H, "P": P, "di": H * P, "N": config["mamba_d_state"],
            "G": config["mamba_n_groups"], "W": config["mamba_d_conv"],
            "gn": 2 * config["mamba_n_groups"] * config["mamba_d_state"],
            "Hq": config["num_attention_heads"],
            "K": config["num_key_value_heads"], "hd": config["head_dim"]}


def _layer_leaves(config, kind):
    """{path: (shape, init, scale)} of one layer, as the program builds it."""
    z = _sizes(config)
    d, f, di, gn = z["d"], z["f"], z["di"], z["gn"]

    def fan(n):
        return np.float64(1.0) / np.sqrt(n)

    leaves = {"ffn.wg": ((d, f), "normal", fan(d)),
              "ffn.wi": ((d, f), "normal", fan(d)),
              "ffn.wo": ((f, d), "normal", fan(f)),
              "ln1": ((d,), "ones", None), "ln2": ((d,), "ones", None)}
    if kind == "attention":
        qd, kvd = z["Hq"] * z["hd"], z["K"] * z["hd"]
        leaves.update({"attn.wq": ((d, qd), "normal", fan(d)),
                       "attn.wk": ((d, kvd), "normal", fan(d)),
                       "attn.wv": ((d, kvd), "normal", fan(d)),
                       "attn.wo": ((qd, d), "normal", fan(qd))})
        return leaves
    H, W = z["H"], z["W"]
    m = {"w_z": ((d, di), "normal", fan(d)), "w_x": ((d, di), "normal", fan(d)),
         "w_bc": ((d, gn), "normal", fan(d)), "w_dt": ((d, H), "normal", fan(d)),
         "conv_x": ((W, di), "normal", 0.5), "conv_bc": ((W, gn), "normal", 0.5),
         "dt_bias": ((H,), "normal", 1.0), "a_log": ((H,), "normal", 1.0),
         "d_skip": ((H,), "ones", None), "norm": ((di,), "ones", None),
         "wo": ((di, d), "normal", fan(di))}
    if config["mamba_conv_bias"]:
        m["conv_x_bias"] = ((di,), "normal", 0.1)
        m["conv_bc_bias"] = ((gn,), "normal", 0.1)
    leaves.update({f"mamba.{k}": v for k, v in m.items()})
    return leaves


def leaf_specs(config):
    """[(path, shape, init, scale)] in the program's flatten order: embed
    (tied to the head, so no head leaf), final_norm, then the segments in
    order, each a stacked run of Mamba-2 layers or one attention layer,
    their leaves in sorted order; ``weights.make`` draws leaf i from
    ``fold_in(key, i)``."""
    Vp, d = config["padded_vocab"], config["hidden_size"]
    out = [("embed", (Vp, d), "embed", 0.02),
           ("final_norm", (d,), "ones", None)]
    for i, (kind, n) in enumerate(segments(config)):
        lead = () if n is None else (n,)
        leaves = _layer_leaves(config, kind)
        out += [(f"segments.{i}.{path}", lead + leaves[path][0],
                 leaves[path][1], leaves[path][2])
                for path in sorted(leaves, key=lambda p: p.split("."))]
    return out


def segment_weights(config, params, si):
    """Segment ``si`` of a ``weights.make`` tree as float32, flat by leaf
    name (``wo`` the mixer's or attention's out-projection, ``wo2`` the
    MLP's); a Mamba-2 run keeps its leading axis of layers."""
    seg = jax.tree.map(lambda x: x.astype(jnp.float32),
                       params["segments"][si])
    p = dict(seg["attn"] if "attn" in seg else seg["mamba"])
    p.update(wg=seg["ffn"]["wg"], wi=seg["ffn"]["wi"], wo2=seg["ffn"]["wo"],
             ln1=seg["ln1"], ln2=seg["ln2"])
    return p


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _q(x, quant):
    return fp8(x) if quant else x


def _conv(x, w, b):
    """Causal depthwise conv, out[t] = sum_k w[k] x[t - (W-1) + k] + b, over
    x: [B, S, C] with zeros before the start; then SiLU."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, k:k + S] * w[k] for k in range(W))
    return jax.nn.silu(out if b is None else out + b)


def recurrence(x, dt, a, b, c, quant=False):
    """The selective scan, step by step.  x: [B, S, H, P]; dt: [B, S, H];
    a: [H]; b, c: [B, S, H, N] (each head's group).  Returns y: [B, S, H, P]
    with y_t = C_t h_t, h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t^T."""
    Bn, S, H, P = x.shape
    N = b.shape[-1]
    blk = min(STEP_BLOCK, S)
    while S % blk:
        blk //= 2

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        upd = _q(b_t * dt_t[..., None], quant)[..., None] * _q(x_t, quant)[..., None, :]
        h = jnp.exp(dt_t * a)[..., None, None] * h + upd
        return h, _mm("bhn,bhnp->bhp", c_t, h, quant)

    block = jax.checkpoint(lambda h, inp: jax.lax.scan(step, h, inp))

    def blocks(t):        # [B, S, ...] -> [S / blk, blk, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(S // blk, blk, *t.shape[1:])

    h0 = jnp.zeros((Bn, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(block, h0, tuple(blocks(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(ys.reshape(S, Bn, H, P), 0, 1)


def mamba_gated(config, p, h, quant=False):
    """The Mamba-2 mixer up to its norm: [B, S, d] -> y * silu(z), [B, S, di]."""
    z = _sizes(config)
    H, P, G, N = z["H"], z["P"], z["G"], z["N"]
    Bn, S, _ = h.shape
    gate = _mm("bsd,df->bsf", h, p["w_z"], quant)
    x = _conv(_mm("bsd,df->bsf", h, p["w_x"], quant), p["conv_x"],
              p.get("conv_x_bias"))
    bc = _conv(_mm("bsd,df->bsf", h, p["w_bc"], quant), p["conv_bc"],
               p.get("conv_bc_bias"))
    dt = jax.nn.softplus(_mm("bsd,dh->bsh", h, p["w_dt"], quant) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    heads = lambda t: jnp.repeat(t.reshape(Bn, S, G, N), H // G, axis=2)  # noqa: E731
    xh = x.reshape(Bn, S, H, P)
    y = recurrence(xh, dt, a, heads(bc[..., :G * N]), heads(bc[..., G * N:]),
                   quant)
    y = y + xh * p["d_skip"][:, None]
    return y.reshape(Bn, S, H * P) * jax.nn.silu(gate)


def group_variance(config, y):
    """Mean square of the gated y over each group's width: [B, S, G, 1]."""
    G = config["mamba_n_groups"]
    yg = y.reshape(*y.shape[:-1], G, -1)
    return jnp.mean(yg * yg, axis=-1, keepdims=True)


def mamba_out(config, p, y, quant=False, variance=None):
    """The gated norm over each group's width, then the out-projection.
    ``variance`` (default: of ``y`` itself) is the mean square it divides
    by; a tensor-parallel share given the whole width's computes its part
    of the deployment's result."""
    G, eps = config["mamba_n_groups"], config["rms_norm_eps"]
    var = group_variance(config, y) if variance is None else variance
    yg = y.reshape(*y.shape[:-1], G, -1) * jax.lax.rsqrt(var + eps)
    y = yg.reshape(y.shape) * p["norm"]
    return _mm("bsf,fd->bsd", y, p["wo"], quant)


def attention(config, p, h, quant=False):
    """NoPE grouped-query causal attention over h: [B, S, d], scores scaled
    by ``attention_multiplier``."""
    Bn, S, _ = h.shape
    z = _sizes(config)
    H, K, hd = z["Hq"], z["K"], z["hd"]
    q = _mm("bsd,df->bsf", h, p["wq"], quant).reshape(Bn, S, H, hd)
    k = _mm("bsd,df->bsf", h, p["wk"], quant).reshape(Bn, S, K, hd)
    v = _mm("bsd,df->bsf", h, p["wv"], quant).reshape(Bn, S, K, hd)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, quant) * config["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, quant).reshape(Bn, S, H * hd)
    return _mm("bsf,fd->bsd", o, p["wo"], quant)


def mlp(p, h, quant=False):
    g = _mm("bsd,df->bsf", h, p["wg"], quant)
    u = _mm("bsd,df->bsf", h, p["wi"], quant)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["wo2"], quant)


def layer(config, kind, p, x, quant=False):
    """One decoder layer over x: [B, S, d] float32."""
    eps, rm = config["rms_norm_eps"], config["residual_multiplier"]
    h = _rmsnorm(x, p["ln1"], eps)
    if kind == "mamba":
        out = mamba_out(config, p, mamba_gated(config, p, h, quant), quant)
    else:
        out = attention(config, p, h, quant)
    x = x + rm * out
    return x + rm * mlp(p, _rmsnorm(x, p["ln2"], eps), quant)


def logits(config, params, tokens, quant=False):
    """[B, S] int tokens -> [B, S, padded_vocab] float32 logits; each run of
    Mamba-2 layers scanned over its stacked weights, each layer
    rematerialised."""
    f32 = jnp.float32
    emb = params["embed"].astype(f32)
    x = jnp.take(emb, tokens, axis=0) * config["embedding_multiplier"]
    for si, (kind, n) in enumerate(segments(config)):
        p = segment_weights(config, params, si)
        body = jax.checkpoint(lambda x, p, kind=kind: (
            layer(config, kind, p, x, quant), None))
        x = body(x, p)[0] if n is None else jax.lax.scan(body, x, p)[0]
    x = _rmsnorm(x, params["final_norm"].astype(f32), config["rms_norm_eps"])
    return _mm("bsd,vd->bsv", x, emb, quant) / config["logits_scaling"]


def serve_logits(config, params, seqs, length, quant=False):
    raise NotImplementedError(
        f"{config['model_type']}: the serving engine keeps no Mamba-2 state "
        "cache, so this architecture has no serving cell")


# ---------------------------------------------------------------------------
# model FLOPs, counted from the shapes: the operations a forward (and
# backward) pass requires, with recomputation left out, causal attention
# counted over the keys each query may see, the SSD's products as the
# chunked algorithm performs them, and the output head where logits are
# needed
# ---------------------------------------------------------------------------

def _counts(config):
    kinds = config["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def layer_matmul_params(config, kind):
    z = _sizes(config)
    d, di = z["d"], z["di"]
    mlp_params = 3 * d * z["f"]
    if kind == "attention":
        qd, kvd = z["Hq"] * z["hd"], z["K"] * z["hd"]
        return d * qd + 2 * d * kvd + qd * d + mlp_params
    return 2 * d * di + d * z["gn"] + d * z["H"] + di * d + mlp_params


def head_params(config):
    return config["hidden_size"] * config["vocab_size"]


def attention_flops(config, n_queries, first_pos=0):
    """Forward FLOPs of scores and values for queries at positions
    first_pos .. first_pos + n_queries - 1 over all attention layers."""
    qd = config["num_attention_heads"] * config["head_dim"]
    keys = n_queries * first_pos + n_queries * (n_queries + 1) // 2
    return 4 * qd * keys * _counts(config)[1]


def chunk_of(config, seq_len):
    """The chunk the program's scan takes at ``seq_len``."""
    c = min(config["mamba_chunk_size"], seq_len)
    while seq_len % c:
        c //= 2
    return c


def ssd_forward_flops(config, batch, seq_len):
    """One forward pass of every Mamba-2 layer's chunked scan: per chunk of
    c steps and head, the scores C B^T (2 c^2 N), their product with the
    values (2 c^2 P), the chunk's state (2 c N P) and the carried state's
    read-out (2 c N P)."""
    z = _sizes(config)
    N, P = z["N"], z["P"]
    c = chunk_of(config, seq_len)
    per_chunk = 2 * c * c * (N + P) + 4 * c * N * P
    return batch * z["H"] * (seq_len // c) * per_chunk * _counts(config)[0]


def train_step_flops(config, batch, seq_len):
    """Forward + backward (3x forward) of one training step."""
    tokens = batch * seq_len
    per_token = sum(layer_matmul_params(config, k)
                    for k in config["layer_types"]) + head_params(config)
    fwd = 2 * tokens * per_token + batch * attention_flops(config, seq_len)
    return 3 * (fwd + ssd_forward_flops(config, batch, seq_len))


def prefill_flops(config, prompt_len):
    return serve_logits(config, None, None, prompt_len)


def decode_flops(config, pos):
    return serve_logits(config, None, None, pos)


def ssd_flops(config, batch, seq_len):
    """FLOPs the ``ssd`` scope runs in one training step: the forward, its
    recomputation (the Mamba-2 segments are rematerialised) and the
    backward at twice the forward."""
    return 4 * ssd_forward_flops(config, batch, seq_len)


def ssd_bytes(config, batch, seq_len):
    """HBM bytes the ``ssd`` scope must move in one training step, at
    least: each pass reads its inputs (x and dt in float32, B and C in the
    compute type) and writes y in float32; the backward reads the inputs
    and dy, and writes a gradient of each input."""
    z = _sizes(config)
    tokens = batch * seq_len
    item = jnp.dtype(config["torch_dtype"]).itemsize
    inputs = tokens * (4 * z["di"] + 4 * z["H"] + item * z["gn"])
    outputs = tokens * 4 * z["di"]
    return _counts(config)[0] * (4 * inputs + 3 * outputs)
