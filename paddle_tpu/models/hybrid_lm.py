"""A served decoder-only trunk built from a configuration: RMSNorm, no
biases, a head of its own or the embedding table's (``tie_embeddings``), a
per-layer mixer kind (``"kda"``: the gated delta rule with per-slot state,
ops/kda.py; ``"mla"``: latent attention over the paged pool, ops/mla.py;
``"mamba"``: a selective scan with per-slot state, ops/mamba.py; ``"attn"``:
softmax attention with grouped K/V over paged K and V pools,
``attn_chunk``; ``"window"``: the same over the last ``window`` positions,
whose K and V live in a per-slot ring; ``"sparse"``: softmax attention
over the positions a learned indexer selects, ``sparse_chunk``) and a
per-layer FFN kind (``"dense"``: a gated SiLU FFN; ``"moe"``: a sigmoid- or
softmax-routed expert layer that holds its share of the experts, plus a
shared expert where the model has one, ops/moe.py).

    x += Attn_l(RMSNorm(x));  x += FFN_l(RMSNorm(x));  logits = RMSNorm(x_L) W_head

With ``post_norms`` each sublayer's result is normed again before it joins
the stream (a sandwich: ``x += RMSNorm(Attn_l(RMSNorm(x)))``, four gains a
layer).  An MLA layer may have a low-rank query with its own norm
(``q_rank``) and rotate its 64 query columns and the shared key part
(``rope_theta``); softmax attention may turn q and k (whole heads or
their leading part, plain or YaRN frequencies) and gate each head's
output.  Five published families build a ``Config``
(``config_from_hf``): ``kimi_linear`` (KDA and unrotated MLA, 3 to 1),
``pangu_ultra_moe`` (MLA in every layer, rotated, a low-rank query,
sandwich norms: a cache of latent pools only, no slot owns state),
``jamba`` (Mamba with one unrotated multi-query attention layer a period,
dense FFNs, a tied head, no positional signal of any kind) and ``laguna``
(window and full attention 3 to 1 with their own head counts and
rotations, per-head output gates, a softmax router) and ``keye`` (sparse
attention in every layer: per-head q/k norms, a lightning indexer whose
keys have their own paged leaf, an exact top-k; a softmax router without
a shared expert).

``DecodeEngine(params, model=Served(cfg))`` serves it through the one
chunked paged step (docs/serving.md "Models that hold state"), whose
residual stream holds the lanes the step's rows feed, packed, at the
narrowest of up to three compiled widths ("The packed lanes").  The cache
has two kinds of leaf, which ``cache_kinds`` declares: the MLA layers'
latent pools and the attention layers' K and V pools are block-addressed; a
KDA or Mamba layer's recurrent state and convolution tail are
slot-addressed, zeroed as data inside the step when a row starts at
position 0, and left alone by lanes past a row's length; a window layer's
ring is slot-addressed too, and needs no zeroing (a lane reads only
positions its row wrote since position 0); a sparse layer's K, V and
indexer keys (``"ik"``) are three block-addressed leaves.

The residual stream, the norms, the router and the recurrence are float32;
matrix products follow ``ops/linear.matmul``."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.transformer import _chunk_lanes
from paddle_tpu.ops import dsa, kda, linear, mamba, mla, moe
from paddle_tpu.serving.kv_pool import BLOCK_LEAF, SLOT_LEAF


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int
    hidden_size: int
    layers: tuple               # ((attention kind, ffn kind), ...)
    rms_norm_eps: float
    # kda
    kda_heads: int
    kda_head_dim: int
    conv_kernel: int
    kda_gate_rank: int
    # mla
    mla_heads: int
    qk_nope: int
    qk_rope: int
    v_head_dim: int
    kv_rank: int
    # ffn
    dense_width: int
    expert_width: int
    router_width: int           # experts the router scores (all of them)
    held: tuple                 # (first, count) of the experts held here
    top_k: int
    routed_scale: float
    shared_experts: int
    # what a family adds to the block
    q_rank: int = None          # MLA: a low-rank query with its own norm
    rope_theta: float = None    # MLA: rotate q's rope columns and k_r
    post_norms: bool = False    # a norm after each sublayer as well
    # mamba
    mamba_inner: int = 0
    mamba_state: int = 0
    mamba_conv: int = 0
    mamba_dt_rank: int = 0
    # attn: softmax attention, ``attn_kv_heads`` K/V heads for all queries
    attn_heads: int = 0
    attn_kv_heads: int = 0
    attn_head_dim: int = 0
    tie_embeddings: bool = False    # the head is the embedding table
    # what attention may add: q and k turned before K is written
    # (``rope_frequencies``: (rotary_dim, inv_freq, cos/sin scale)), a
    # per-head sigmoid gate before W_o, and the "window" layers' own heads,
    # rotation and window (0: they attend every position, over the pool)
    attn_rope: tuple = None
    attn_gate: bool = False
    window_heads: int = 0
    window_rope: tuple = None
    window: int = 0
    router: str = "sigmoid"     # or "softmax": over all experts, no bias
    # "sparse" layers (q and k each RMS-normed a head before rotation):
    # the indexer's heads, rotation and the positions a lane keeps
    index_heads: int = 0
    index_dim: int = 0
    index_rope: tuple = None
    index_topk: int = 0

    @property
    def latent_width(self):
        return self.kv_rank + self.qk_rope

    @property
    def kda_width(self):
        return self.kda_heads * self.kda_head_dim

    def attention(self, kind):
        """``attn_chunk``'s keywords for a layer of ``kind`` ("attn" or
        "window")."""
        window = kind == "window"
        return dict(num_heads=self.window_heads if window
                    else self.attn_heads,
                    kv_heads=self.attn_kv_heads, head_dim=self.attn_head_dim,
                    rope=self.window_rope if window else self.attn_rope,
                    gate=self.attn_gate,
                    window=self.window if window else 0)


def config_from_hf(c):
    """``Config`` from a published ``config.json`` (a dict), by the
    mechanisms its keys state and not by ``model_type``: the layers of
    ``linear_attn_config`` are KDA and the rest MLA; ``q_lora_rank`` is a
    low-rank query; ``sandwich_norm`` the norms after the sublayers;
    ``rope_theta`` rotates the small key part unless ``mla_use_nope`` says
    the published base is unused.  The families' synonyms for the routed
    layer are read by presence.  Plus the groups a cut adds:
    ``assumed.kda_gate_rank`` and ``expert_parallel`` (the key that counts
    the routed experts then gives those held of ``num_experts_published``,
    by rank ``rank``).  ``mamba_d_state`` and ``attn_layer_period`` are the
    third family (``_jamba_config``), ``layer_types`` with
    ``num_attention_heads_per_layer`` the fourth (``_laguna_config``),
    ``sa_config`` the fifth (``_keye_config``)."""
    if "mamba_d_state" in c and "attn_layer_period" in c:
        return _jamba_config(c)
    if "layer_types" in c and "num_attention_heads_per_layer" in c:
        return _laguna_config(c)
    if "sa_config" in c:
        return _keye_config(c)

    def either(*keys):
        return next(c[k] for k in keys if k in c)
    la = c.get("linear_attn_config")
    full = set(la["full_attn_layers"]) if la else None
    ep = c.get("expert_parallel") or {}
    count = either("n_routed_experts", "num_experts")
    theta = None if c.get("mla_use_nope") else c.get("rope_theta")
    return Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        layers=tuple(("mla" if full is None or l in full else "kda",
                      "dense" if l <= c["first_k_dense_replace"] else "moe")
                     for l in range(1, c["num_hidden_layers"] + 1)),
        rms_norm_eps=c["rms_norm_eps"],
        kda_heads=la["num_heads"] if la else 0,
        kda_head_dim=la["head_dim"] if la else 0,
        conv_kernel=la["short_conv_kernel_size"] if la else 0,
        kda_gate_rank=(c.get("assumed") or {}).get(
            "kda_gate_rank", la["head_dim"]) if la else 0,
        mla_heads=c["num_attention_heads"], qk_nope=c["qk_nope_head_dim"],
        qk_rope=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        kv_rank=c["kv_lora_rank"], dense_width=c["intermediate_size"],
        expert_width=c["moe_intermediate_size"],
        router_width=ep.get("num_experts_published", count),
        held=(ep.get("rank", 0) * count, count),
        top_k=either("num_experts_per_tok", "num_experts_per_token"),
        routed_scale=c["routed_scaling_factor"],
        shared_experts=either("n_shared_experts", "num_shared_experts"),
        q_rank=c.get("q_lora_rank"),
        rope_theta=float(theta) if theta else None,
        post_norms=bool(c.get("sandwich_norm")))


def _jamba_config(c):
    """The ``jamba`` family: layer i (from 0) attends when ``i %
    attn_layer_period == attn_layer_offset`` and is a Mamba layer otherwise
    (``layers_block_type`` of its ``configuration_jamba.py``); every
    feed-forward part is the dense gated FFN, which is what ``num_experts``
    1 makes of ``expert_layer_*``."""
    if c["num_experts"] != 1:
        raise NotImplementedError(
            "routed experts inside a state-space model (num_experts "
            f"{c['num_experts']}): the jamba family is served with dense "
            "FFNs only")
    if c.get("sliding_window"):
        raise NotImplementedError("window attention layers are not served")
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return Config(
        vocab_size=c["vocab_size"], hidden_size=d,
        layers=tuple(
            ("attn" if i % c["attn_layer_period"] == c["attn_layer_offset"]
             else "mamba", "dense") for i in range(c["num_hidden_layers"])),
        rms_norm_eps=c["rms_norm_eps"],
        kda_heads=0, kda_head_dim=0, conv_kernel=0, kda_gate_rank=0,
        mla_heads=0, qk_nope=0, qk_rope=0, v_head_dim=0, kv_rank=0,
        dense_width=c["intermediate_size"], expert_width=0, router_width=0,
        held=(0, 0), top_k=0, routed_scale=0.0, shared_experts=0,
        mamba_inner=c["mamba_expand"] * d, mamba_state=c["mamba_d_state"],
        mamba_conv=c["mamba_d_conv"], mamba_dt_rank=c["mamba_dt_rank"],
        attn_heads=heads, attn_kv_heads=c["num_key_value_heads"],
        attn_head_dim=c.get("head_dim") or d // heads,
        tie_embeddings=bool(c["tie_word_embeddings"]))


LAGUNA_KINDS = {"full_attention": "attn", "sliding_attention": "window"}


def _laguna_config(c):
    """The ``laguna`` family: layer i's attention is ``layer_types[i]``
    (full, or a window of ``sliding_window`` positions; each with its own
    ``rope_parameters`` and its own ``num_attention_heads_per_layer[i]``
    query heads, which must be alike within a kind), all on
    ``num_key_value_heads`` K/V heads, a per-head output gate
    (``gating``); its FFN is ``mlp_layer_types[i]``: the dense gated FFN or
    a softmax-routed expert layer with a shared expert.  The first
    ``num_hidden_layers`` entries of the lists are read, so a cut in depth
    is that key alone; ``expert_parallel`` as for the other families."""
    if not c.get("norm_topk_prob", True) \
            or c.get("moe_router_logit_softcapping") \
            or c.get("moe_apply_router_weight_on_input"):
        raise NotImplementedError(
            "the softmax router is served with its chosen shares "
            "renormalised, no soft cap and its weights on the outputs")
    n = c["num_hidden_layers"]
    kinds = [LAGUNA_KINDS[t] for t in c["layer_types"][:n]]
    heads = {}
    for kind, h in zip(kinds, c["num_attention_heads_per_layer"][:n]):
        if heads.setdefault(kind, h) != h:
            raise NotImplementedError(
                f"{kind} layers with {heads[kind]} and {h} query heads: one "
                "count a kind is served")
    dh = c["head_dim"]
    ep = c.get("expert_parallel") or {}
    count = c["num_experts"]
    rope = {kind: rope_frequencies(dh, c["rope_parameters"][t])
            for t, kind in LAGUNA_KINDS.items()}
    return Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        layers=tuple((kind, "dense" if f == "dense" else "moe")
                     for kind, f in zip(kinds, c["mlp_layer_types"][:n])),
        rms_norm_eps=c["rms_norm_eps"],
        kda_heads=0, kda_head_dim=0, conv_kernel=0, kda_gate_rank=0,
        mla_heads=0, qk_nope=0, qk_rope=0, v_head_dim=0, kv_rank=0,
        dense_width=c["intermediate_size"],
        expert_width=c["moe_intermediate_size"],
        router_width=ep.get("num_experts_published", count),
        held=(ep.get("rank", 0) * count, count),
        top_k=c["num_experts_per_tok"],
        routed_scale=float(c["moe_routed_scaling_factor"]),
        shared_experts=c["shared_expert_intermediate_size"]
        // c["moe_intermediate_size"],
        attn_heads=heads.get("attn", 0), attn_kv_heads=c["num_key_value_heads"],
        attn_head_dim=dh, tie_embeddings=bool(c["tie_word_embeddings"]),
        attn_rope=rope["attn"], attn_gate=bool(c.get("gating")),
        window_heads=heads.get("window", 0), window_rope=rope["window"],
        window=int(c.get("sliding_window") or 0), router="softmax")


def _keye_config(c):
    """The ``keye`` family (Keye-VL-2.0's language model, Qwen3-MoE-shaped):
    every layer is a ``"sparse"`` attention layer (``num_attention_heads``
    query heads on ``num_key_value_heads``, q and k RMS-normed a head, all
    of each head turned at ``rope_theta``; a lightning indexer of
    ``sa_config.indexer_num_heads`` heads of ``indexer_head_dim`` on one
    key head, the leading half of its head turned, keeping ``topk``
    positions a lane) and a softmax-routed expert layer with its chosen
    shares renormalised and no shared expert.  ``rope_scaling.
    mrope_section`` must cover half a head: a text token's three positions
    are equal, and the rotation is the plain one.  ``expert_parallel`` as
    for the other families."""
    sa = c["sa_config"]
    section = (c.get("rope_scaling") or {}).get("mrope_section")
    dh = c["head_dim"]
    if section and 2 * sum(section) != dh:
        raise NotImplementedError(
            f"mrope_section {section} does not cover half a head of {dh}")
    if sa.get("indexer_num_kv_heads", 1) != 1 or c.get("mlp_only_layers") \
            or c.get("decoder_sparse_step", 1) != 1 \
            or c.get("use_sliding_window") \
            or not c.get("norm_topk_prob", True):
        raise NotImplementedError(
            "the keye family is served with one indexer key head, an "
            "expert layer in every layer, no window and renormalised "
            "shares")
    ep = c.get("expert_parallel") or {}
    count = c["num_experts"]
    theta = float(c["rope_theta"])
    di = sa["indexer_head_dim"]
    return Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        layers=(("sparse", "moe"),) * c["num_hidden_layers"],
        rms_norm_eps=c["rms_norm_eps"],
        kda_heads=0, kda_head_dim=0, conv_kernel=0, kda_gate_rank=0,
        mla_heads=0, qk_nope=0, qk_rope=0, v_head_dim=0, kv_rank=0,
        dense_width=c["intermediate_size"],
        expert_width=c["moe_intermediate_size"],
        router_width=ep.get("num_experts_published", count),
        held=(ep.get("rank", 0) * count, count),
        top_k=c["num_experts_per_tok"], routed_scale=1.0, shared_experts=0,
        attn_heads=c["num_attention_heads"],
        attn_kv_heads=c["num_key_value_heads"], attn_head_dim=dh,
        tie_embeddings=bool(c["tie_word_embeddings"]),
        attn_rope=rope_frequencies(dh, {"rope_theta": theta}),
        router="softmax",
        index_heads=sa["indexer_num_heads"], index_dim=di,
        index_rope=rope_frequencies(di, {"rope_theta": theta,
                                         "partial_rotary_factor": 0.5}),
        index_topk=sa["topk"])


def rope_frequencies(head_dim, spec):
    """One kind of layer's rotation from its ``rope_parameters`` entry ->
    (rotary_dim, inv_freq (rotary_dim / 2 floats), cos/sin scale).  The
    leading ``partial_rotary_factor`` of each head turns, in rotate-half
    pairs (i, i + rotary_dim / 2) by position x inv_freq_i;
    ``rope_type`` "yarn" blends each frequency with its ``factor``-fold
    slower twin by Hugging Face's ``_compute_yarn_parameters`` (the ramp
    between the dimensions that turn ``beta_fast`` and ``beta_slow`` times
    over ``original_max_position_embeddings``) and scales cos and sin by
    ``attention_factor`` (or 0.1 ln(factor) + 1)."""
    dim = int(head_dim * spec.get("partial_rotary_factor", 1.0))
    base = float(spec["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0
    if spec.get("rope_type", "default") == "yarn":
        factor = float(spec["factor"])
        orig = spec["original_max_position_embeddings"]

        def turns_at(rot):      # the dimension that turns ``rot`` times
            return dim * math.log(orig / (rot * 2 * math.pi)) \
                / (2 * math.log(base))
        low = max(math.floor(turns_at(spec.get("beta_fast", 32))), 0)
        high = min(math.ceil(turns_at(spec.get("beta_slow", 1))), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        scale = float(spec.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0)
    return dim, tuple(float(f) for f in np.float32(inv)), scale


# ------------------------------------------------------------ parameters

def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _init_attn(key, cfg, kind, dtype):
    d = cfg.hidden_size
    ks = jax.random.split(key, 12)
    lin = lambda k, i, o: _normal(k, (i, o), i ** -0.5, dtype)
    if kind == "mla":
        qw = cfg.mla_heads * (cfg.qk_nope + cfg.qk_rope)
        query = {"wq": lin(ks[0], d, qw)} if cfg.q_rank is None else {
            "wqa": lin(ks[0], d, cfg.q_rank),
            "q_norm": jnp.ones((cfg.q_rank,), jnp.float32),
            "wqb": lin(ks[4], cfg.q_rank, qw)}
        return {
            **query,
            "wkva": lin(ks[1], d, cfg.latent_width),
            "kv_norm": jnp.ones((cfg.kv_rank,), jnp.float32),
            "wkvb": lin(ks[2], cfg.kv_rank,
                        cfg.mla_heads * (cfg.qk_nope + cfg.v_head_dim)),
            "wo": lin(ks[3], cfg.mla_heads * cfg.v_head_dim, d)}
    if kind in ("attn", "window"):
        dh, heads = cfg.attn_head_dim, cfg.attention(kind)["num_heads"]
        out = {"wqkv": lin(ks[0], d, (heads + 2 * cfg.attn_kv_heads) * dh),
               "wo": lin(ks[1], heads * dh, d)}
        if cfg.attn_gate:
            out["wgate"] = lin(ks[2], d, heads)
        return out
    if kind == "sparse":
        dh, heads, di = cfg.attn_head_dim, cfg.attn_heads, cfg.index_dim
        return {"wqkv": lin(ks[0], d, (heads + 2 * cfg.attn_kv_heads) * dh),
                "wo": lin(ks[1], heads * dh, d),
                "q_norm": jnp.ones((dh,), jnp.float32),
                "k_norm": jnp.ones((dh,), jnp.float32),
                "wq_index": lin(ks[2], d, cfg.index_heads * di),
                "wk_index": lin(ks[3], d, di),
                "k_index_norm": jnp.ones((di,), jnp.float32),
                "k_index_bias": jnp.zeros((di,), jnp.float32),
                "w_index": lin(ks[4], d, cfg.index_heads)}
    if kind == "mamba":
        di, n, r = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank
        # Mamba's own start: A = 1..n in every column, D = 1, dt in
        # [1e-3, 1e-1] through the inverse softplus
        dt = jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "w_in": lin(ks[0], d, 2 * di),
            "conv": _normal(ks[1], (cfg.mamba_conv, di),
                            cfg.mamba_conv ** -0.5, jnp.float32),
            "conv_bias": jnp.zeros((di,), jnp.float32),
            "w_x": lin(ks[2], di, r + 2 * n),
            "dt_norm": jnp.ones((r,), jnp.float32),
            "b_norm": jnp.ones((n,), jnp.float32),
            "c_norm": jnp.ones((n,), jnp.float32),
            "w_dt": lin(ks[3], r, di),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            # [n, d_inner]: d_state on sublanes, as the state lies
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                (n, di)),
            "d": jnp.ones((di,), jnp.float32),
            "w_out": lin(ks[5], di, d)}
    w, r = cfg.kda_width, cfg.kda_gate_rank
    # flash-linear-attention's own start: A in [1, 16], dt in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(ks[4], (w,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "wqkv": lin(ks[0], d, 3 * w),
        "conv": _normal(ks[1], (cfg.conv_kernel, 3 * w),
                        cfg.conv_kernel ** -0.5, jnp.float32),
        "wf1": lin(ks[2], d, r), "wf2": lin(ks[3], r, w),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(ks[5], (cfg.kda_heads,),
                                            jnp.float32, 1.0, 16.0)),
        "wb": lin(ks[6], d, cfg.kda_heads),
        "wg1": lin(ks[7], d, r), "wg2": lin(ks[8], r, w),
        "o_norm": jnp.ones((cfg.kda_head_dim,), jnp.float32),
        "wo": lin(ks[9], w, d)}


def _init_ffn(key, cfg, kind, dtype):
    d = cfg.hidden_size
    ks = jax.random.split(key, 7)

    def gated(k3, width, lead=()):
        return {"wg": _normal(k3[0], lead + (d, width), d ** -0.5, dtype),
                "wu": _normal(k3[1], lead + (d, width), d ** -0.5, dtype),
                "wd": _normal(k3[2], lead + (width, d), width ** -0.5,
                              dtype)}
    if kind == "dense":
        return gated(ks[:3], cfg.dense_width)
    out = gated(ks[:3], cfg.expert_width, (cfg.held[1],))
    out["router"] = _normal(ks[3], (d, cfg.router_width), d ** -0.5,
                            jnp.float32)
    if cfg.router == "sigmoid":
        out["router_bias"] = jnp.zeros((cfg.router_width,), jnp.float32)
    if cfg.shared_experts:
        out["shared"] = gated(jax.random.split(ks[4], 3),
                              cfg.expert_width * cfg.shared_experts)
    return out


def _init_layer(key, cfg, kinds, dtype):
    ka, kf = jax.random.split(key)
    d = cfg.hidden_size
    gains = ("norm1", "norm2") + (("post_attn", "post_ffn")
                                  if cfg.post_norms else ())
    return {**{g: jnp.ones((d,), jnp.float32) for g in gains},
            "attn": _init_attn(ka, cfg, kinds[0], dtype),
            "ffn": _init_ffn(kf, cfg, kinds[1], dtype)}


def init(key, cfg, dtype=jnp.float32, emb_std=0.02):
    """Seeded parameters: N(0, 1/fan_in) projections, N(0, emb_std)
    embeddings (no ``head`` leaf where the table is the head), gains at 1,
    the router bias at 0.  ``dtype`` is that of the
    matrices; gains, biases, the convolution and the router stay float32.
    Made one layer a jitted call, so that a model that nearly fills the
    device is never beside a second copy of itself."""
    keys = jax.random.split(key, len(cfg.layers) + 2)
    d, v = cfg.hidden_size, cfg.vocab_size
    layer = lambda kinds: jax.jit(functools.partial(
        _init_layer, cfg=cfg, kinds=kinds, dtype=dtype))
    by_kind = {kinds: layer(kinds) for kinds in set(cfg.layers)}
    table = jax.jit(lambda k, shape, std: _normal(k, shape, std, dtype),
                    static_argnums=(1, 2))
    head = {} if cfg.tie_embeddings else {
        "head": table(keys[1], (d, v), d ** -0.5)}
    return {"emb": table(keys[0], (v, d), emb_std), **head,
            "norm_f": jnp.ones((d,), jnp.float32),
            "layers": [by_kind[kinds](k)
                       for kinds, k in zip(cfg.layers, keys[2:])]}


# ----------------------------------------------------------------- cache

def ring_positions(cfg, block, chunk):
    """Positions of a window layer's per-slot ring: the window and a
    chunk's lanes less one, in whole blocks, so that a step's own writes
    never overwrite a position one of its lanes reads."""
    return -(-(cfg.window + chunk - 1) // block) * block


def init_cache(cfg, slots, blocks, block, latent_dtype=jnp.float32,
               chunk=1):
    """One entry a layer.  KDA: ``{"state" [slots, H, dk, dv] float32,
    "conv" [slots, W-1, 3*H*dk] float32}``, owned by the slot; MLA:
    ``{"latent" [blocks, block, pool_width(rank + rope)]}``, addressed
    through the block tables (block 0 is the scratch block free rows point
    at).  Mamba: ``{"state" [slots, n, d_inner] float32, "conv" [slots, W-1,
    d_inner] float32}``, owned by the slot; attn: ``{"k", "v" [blocks,
    block, kv heads x head dim]}`` in the pools' dtype, addressed through
    the tables; window (with ``cfg.window``): ``{"k", "v" [slots,
    ring_positions(block, chunk) / block, block, kv heads x head dim]}``,
    a RING owned by the slot (position p at ``p % ring``), for steps of
    ``chunk`` lanes a row at most; sparse: the attn pools and ``{"ik"
    [blocks, block, pool_width(indexer head dim)]}``, the indexer's keys,
    beside them (a 64-wide leaf would take a whole 128-lane tile of HBM
    anyway, and a copy of it must be whole tiles: the lanes past the head
    stay zero)."""
    h, dk = cfg.kda_heads, cfg.kda_head_dim

    def layer(kind):
        if kind == "window" and cfg.window:
            shape = (slots, ring_positions(cfg, block, chunk) // block,
                     block, cfg.attn_kv_heads * cfg.attn_head_dim)
            return {"k": jnp.zeros(shape, latent_dtype),
                    "v": jnp.zeros(shape, latent_dtype)}
        if kind == "kda":
            return {"state": jnp.zeros((slots, h, dk, dk), jnp.float32),
                    "conv": jnp.zeros((slots, cfg.conv_kernel - 1,
                                       3 * h * dk), jnp.float32)}
        if kind == "mamba":
            return {"state": jnp.zeros((slots, cfg.mamba_state,
                                        cfg.mamba_inner), jnp.float32),
                    "conv": jnp.zeros((slots, cfg.mamba_conv - 1,
                                       cfg.mamba_inner), jnp.float32)}
        if kind in ("attn", "window", "sparse"):
            shape = (blocks, block, cfg.attn_kv_heads * cfg.attn_head_dim)
            out = {"k": jnp.zeros(shape, latent_dtype),
                   "v": jnp.zeros(shape, latent_dtype)}
            if kind == "sparse":
                out["ik"] = jnp.zeros(
                    (blocks, block, mla.pool_width(cfg.index_dim)),
                    latent_dtype)
            return out
        return {"latent": jnp.zeros(
            (blocks, block, mla.pool_width(cfg.latent_width)), latent_dtype)}

    return [layer(kind) for kind, _ffn in cfg.layers]


_LEAF_KINDS = {"kda": {"state": SLOT_LEAF, "conv": SLOT_LEAF},
               "mamba": {"state": SLOT_LEAF, "conv": SLOT_LEAF},
               "attn": {"k": BLOCK_LEAF, "v": BLOCK_LEAF},
               "window": {"k": SLOT_LEAF, "v": SLOT_LEAF},
               "sparse": {"k": BLOCK_LEAF, "v": BLOCK_LEAF,
                          "ik": BLOCK_LEAF},
               "mla": {"latent": BLOCK_LEAF}}


def cache_kinds(cfg):
    """The cache's tree with ``kv_pool.SLOT_LEAF`` / ``BLOCK_LEAF`` in
    place of each buffer (a window layer without a window keeps the
    pools)."""
    return [dict(_LEAF_KINDS["attn" if kind == "window" and not cfg.window
                             else kind]) for kind, _ffn in cfg.layers]


# ------------------------------------------------------------------ step

STEP_WIDTH_FRACTIONS = (4, 2, 1)    # of S x K, narrowest first


def step_widths(s, kk):
    """The packed widths ``Served.decode_chunk`` is compiled at: a quarter,
    a half and the whole of the step's ``S x K`` lanes, those that hold a
    lane of every row (each row feeds at least one)."""
    return tuple(sorted({s * kk // f for f in STEP_WIDTH_FRACTIONS
                         if s * kk // f >= s}))


def pack_lanes(lengths, kk):
    """The step's live lanes laid side by side, on the host (numpy):
    lengths ``[S]`` in ``[1, K]`` -> (src ``[S x K]`` int32, back ``[S, K]``
    int32).  ``src[n]`` is the lane (``row * K + lane``) that packed place
    ``n`` holds, rows in order and each row's lanes in order; the places
    past the live count repeat the last live lane.  ``back[r, j]`` is the
    place of row r's lane j, a lane past the row's length that of the row's
    last.  Any leading ``src[:N]`` that holds every live lane is a packing
    at width ``N``.  A free slot is armed at one lane like any row and so
    takes one place: whatever a kernel reads of its row is finite."""
    lengths = np.asarray(lengths)
    lane = np.arange(kk)[None, :]
    live = np.flatnonzero(lane < lengths[:, None])
    src = np.full(lengths.size * kk, live[-1], np.int32)
    src[:live.size] = live
    first = np.cumsum(lengths) - lengths
    back = first[:, None] + np.minimum(lane, lengths[:, None] - 1)
    return src, back.astype(np.int32)


def rotate(x, pos, heads, head_dim, rope):
    """x ``[N, heads x head_dim]`` float32 at positions pos ``[N]`` -> the
    same with the leading ``rotary_dim`` of each head turned in rotate-half
    pairs (i, i + rotary_dim / 2) by pos x inv_freq_i, cos and sin times
    the scale (``rope_frequencies``)."""
    dim, inv, scale = rope
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x = x.reshape(-1, heads, head_dim)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], -1).reshape(-1, heads * head_dim)


def attn_chunk(p, h, k_pool, v_pool, qpos, tables, src, back, *, num_heads,
               kv_heads, head_dim, rope=None, gate=False, window=0):
    """One softmax attention layer with grouped K/V over the step's packed
    lanes.  p: ``wqkv`` (q | k | v columns) and ``wo`` (and ``wgate`` [d,
    H] with ``gate``), h ``[N, d]`` the normed input of the packed lanes,
    k_pool / v_pool ``[blocks, block, kv_heads x head_dim]``, qpos ``[S,
    K]`` the lanes' positions, tables ``[S, blocks_per_row]``, src ``[N]``
    / back ``[S, K]`` the packing -> (y ``[N, d]``, new K pool, new V
    pool).  ``rope`` (``rope_frequencies``) turns q and k before K is
    written, so the pool holds turned keys; ``gate`` scales each head's
    output by ``sigmoid(h W_gate)`` before ``W_o``; without either nothing
    is turned or scaled.

    The projections run on the ``N`` packed lanes and K and V are written
    from them, position by position, BEFORE the read (a place that repeats
    a lane writes nothing), so causality inside the chunk is the ordinary
    mask.  The attention keeps ``[S, K]`` rows: the paged decode kernel
    (``decode_attention.maybe_paged_chunk``), or where it declines each
    row's blocks gathered through its table and ``[S, K, H, T]`` scores.

    ``window`` W: lane i attends ``(qpos_i - W, qpos_i]`` and k_pool /
    v_pool are per-slot RINGS ``[S, R / block, block, kv_heads x
    head_dim]`` (position p at ``p % R``, R at least W + K - 1:
    ``ring_positions``) that no table addresses: the window kernel
    (``decode_attention.maybe_window_chunk``, walking a ring a block at a
    time) or, where it declines, ``[S, K, H, R]`` scores over the
    rings."""
    from paddle_tpu.ops.pallas import decode_attention
    s, kk = qpos.shape
    block, dkv = k_pool.shape[1], kv_heads * head_dim
    d_q = num_heads * head_dim
    qkv = linear.matmul(h, p["wqkv"])
    row, pos = src // kk, qpos.reshape(-1)[src]
    if window:
        ring_shape = k_pool.shape
        block = ring_shape[2]
        ring = ring_shape[1] * block
        if ring < window + kk - 1:
            raise ValueError(
                f"a ring of {ring} positions does not hold a window of "
                f"{window} and a chunk of {kk} lanes")
        k_pool, v_pool = (c.reshape(s, ring, dkv) for c in (k_pool, v_pool))
        slot = jnp.where(mla.own_places(src, back), row, s)
        at = lambda: (slot, pos % ring)
    else:
        blk = jnp.where(mla.own_places(src, back), tables[row, pos // block],
                        k_pool.shape[0])
        at = lambda: (blk, pos % block)
    # the index is formed at each write, as the unturned, unwindowed layer
    # always formed it: that layer compiles to the program it did
    write = lambda pool, new: pool.at[at()].set(new.astype(pool.dtype),
                                                mode="drop")
    k = qkv[:, d_q:d_q + dkv]
    k_pool = write(k_pool, k if rope is None
                   else rotate(k, pos, kv_heads, head_dim, rope))
    v_pool = write(v_pool, qkv[:, d_q + dkv:])
    q = qkv[:, :d_q]
    if rope is not None:
        q = rotate(q, pos, num_heads, head_dim, rope)
    q = q.astype(k_pool.dtype)[back]                        # [S, K, H x dh]
    if window:
        o = decode_attention.maybe_window_chunk(
            q, k_pool, v_pool, qpos, num_heads, window, block=block,
            entries=tables.shape[1])
        if o is None:
            o = _ring_attention(q, k_pool, v_pool, qpos, kv_heads, head_dim,
                                window)
        k_pool, v_pool = (c.reshape(ring_shape) for c in (k_pool, v_pool))
    else:
        o = decode_attention.maybe_paged_chunk(q, k_pool, v_pool, qpos,
                                               tables, num_heads)
    if o is None:
        group = num_heads // kv_heads
        rows = lambda pool: pool[tables].reshape(s, -1, kv_heads, head_dim)
        keys, values = rows(k_pool), rows(v_pool)
        scores = linear.einsum(
            "skvgd,stvd->skvgt", q.reshape(s, kk, kv_heads, group, head_dim),
            keys) * head_dim ** -0.5
        live = jnp.arange(keys.shape[1])[None, None, :] <= qpos[:, :, None]
        probs = jax.nn.softmax(
            jnp.where(live[:, :, None, None, :], scores, -jnp.inf), axis=-1)
        o = linear.einsum("skvgt,stvd->skvgd", probs, values)
    o = o.reshape(s * kk, d_q)[src]
    if gate:
        g = jax.nn.sigmoid(linear.matmul(h, p["wgate"]))      # [N, H]
        o = (o.reshape(-1, num_heads, head_dim) * g[:, :, None]) \
            .reshape(-1, d_q)
    return linear.matmul(o, p["wo"]), k_pool, v_pool


def _ring_attention(q, k_ring, v_ring, qpos, kv_heads, head_dim, window):
    """The window's XLA path: ``[S, K, H, R]`` scores over each row's
    ring.  Ring place i holds the latest position at or below the row's
    last lane that is ``i`` modulo R, which the step has just written or
    an earlier one did; a place the row has not written yet holds a
    negative position, and the mask drops it."""
    s, kk, d_q = q.shape
    ring = k_ring.shape[1]
    group = d_q // (kv_heads * head_dim)
    last = qpos[:, -1:]                                        # [S, 1]
    held = last - (last - jnp.arange(ring)[None, :]) % ring    # [S, R]
    live = (held[:, None, :] <= qpos[:, :, None]) \
        & (held[:, None, :] > qpos[:, :, None] - window) \
        & (held[:, None, :] >= 0)                              # [S, K, R]
    rows = lambda ring_: ring_.reshape(s, ring, kv_heads, head_dim)
    scores = linear.einsum(
        "skvgd,srvd->skvgr", q.reshape(s, kk, kv_heads, group, head_dim),
        rows(k_ring)) * head_dim ** -0.5
    probs = jax.nn.softmax(
        jnp.where(live[:, :, None, None, :], scores, -jnp.inf), axis=-1)
    return linear.einsum("skvgr,srvd->skvgd", probs, rows(v_ring))


def head_norm(x, gain, heads, head_dim, eps):
    """x ``[N, heads x head_dim]`` RMS-normed a head, one ``[head_dim]``
    gain for all heads."""
    return kda.rms_norm(x.reshape(-1, heads, head_dim), gain, eps) \
        .reshape(-1, heads * head_dim)


def _layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def sparse_chunk(p, h, k_pool, v_pool, ik_pool, qpos, tables, src, back, *,
                 num_heads, kv_heads, head_dim, rope, index_heads, index_dim,
                 index_rope, topk, eps):
    """One sparse attention layer over the step's packed lanes (DeepSeek
    Sparse Attention's lightning indexer before GQA softmax attention,
    ops/dsa.py).  p: ``wqkv``, ``wo``, ``q_norm`` / ``k_norm`` (a head's
    RMSNorm gains), ``wq_index`` [d, index_heads x index_dim],
    ``wk_index`` [d, index_dim] with the LayerNorm ``k_index_norm`` /
    ``k_index_bias``, ``w_index`` [d, index_heads]; h ``[N, d]`` the normed
    input; the K, V and indexer-key pools ``[blocks, block, .]``; qpos,
    tables, src, back as ``attn_chunk`` takes them -> (y ``[N, d]``, the
    three pools, the positions each lane took as bits ``[S, K, W]`` int32,
    ``ops/dsa.py``'s layout).

    q and k are normed a head, then turned (``rope``); the indexer's query
    ``h W_qI`` and key ``LayerNorm(h W_kI)`` turn by ``index_rope``; the
    weights are ``h W_w / sqrt(index_heads x index_dim)``.  K, V and the
    indexer's key are written BEFORE the reads, as ``attn_chunk`` writes
    K and V; each lane then keeps the ``topk`` positions at or before its
    own with the largest ``sum_j w_j ReLU(q_j . k_s)`` and attends those
    alone, all its heads alike."""
    s, kk = qpos.shape
    block, dkv = k_pool.shape[1], kv_heads * head_dim
    d_q = num_heads * head_dim
    qkv = linear.matmul(h, p["wqkv"])
    row, pos = src // kk, qpos.reshape(-1)[src]
    blk = jnp.where(mla.own_places(src, back), tables[row, pos // block],
                    k_pool.shape[0])
    write = lambda pool, new: pool.at[blk, pos % block].set(
        new.astype(pool.dtype), mode="drop")
    k = rotate(head_norm(qkv[:, d_q:d_q + dkv], p["k_norm"], kv_heads,
                         head_dim, eps), pos, kv_heads, head_dim, rope)
    k_pool = write(k_pool, k)
    v_pool = write(v_pool, qkv[:, d_q + dkv:])
    q = rotate(head_norm(qkv[:, :d_q], p["q_norm"], num_heads, head_dim, eps),
               pos, num_heads, head_dim, rope)
    # the indexer's key and queries, widened with zeros to the key leaf's
    # width (``init_cache``): the extra lanes add nothing to a product
    wide = lambda x, heads: jnp.pad(
        x.reshape(-1, heads, index_dim),
        ((0, 0), (0, 0), (0, ik_pool.shape[2] - index_dim)))
    ik = _layer_norm(linear.matmul(h, p["wk_index"]), p["k_index_norm"],
                     p["k_index_bias"], eps)
    ik_pool = write(ik_pool, wide(rotate(ik, pos, 1, index_dim, index_rope),
                                  1)[:, 0])
    qi = wide(rotate(linear.matmul(h, p["wq_index"]), pos, index_heads,
                     index_dim, index_rope), index_heads)
    w = linear.matmul(h, p["w_index"]) * (index_heads * index_dim) ** -0.5
    from paddle_tpu.ops.pallas import dsa as dsa_kernels
    kernel = dsa_kernels.decline_reason(
        num_heads, d_q, dkv, block, tables.shape[1], kk, index_heads) is None
    scores = dsa.index_scores(qi.astype(ik_pool.dtype)[back], w[back],
                              ik_pool, qpos, tables, kernel)
    picks, bits = dsa.select(scores, qpos, topk, kernel)
    o = dsa.attend(q.astype(k_pool.dtype)[back], k_pool, v_pool, scores,
                   picks, qpos, tables, num_heads, kernel)
    o = o.reshape(s * kk, d_q)[src]
    return linear.matmul(o, p["wo"]), k_pool, v_pool, ik_pool, bits


def decode_chunk(params, cfg, tokens, positions, lengths, cache, tables,
                 with_routes=False, packing=None):
    """``decode_chunk_report``'s logits and new cache, and with
    ``with_routes`` its chosen experts."""
    logits, cache, routes, _picks = decode_chunk_report(
        params, cfg, tokens, positions, lengths, cache, tables, packing)
    return (logits, cache, routes) if with_routes else (logits, cache)


def decode_chunk_report(params, cfg, tokens, positions, lengths, cache,
                        tables, packing=None):
    """``lm_decode_chunk_paged``'s lane semantics: tokens ``[S, K]``,
    positions ``[S]`` (lane 0's), lengths ``[S]`` in ``[1, K]``; row r
    advances ``lengths[r]`` positions.  -> (logits ``[S, V]`` at each
    row's last fed lane, new cache, the chosen experts ``[S, K, top_k]`` of
    every expert layer, in layer order (a lane past its row's length
    repeats the row's last), and the positions each sparse layer's lanes
    took, ``[S, K, W]`` bits (``sparse_chunk``; an empty list for a model
    without sparse layers)).  A row at position 0 starts from zero state;
    lengths and positions are data.

    The residual stream lives on a packed axis ``[N, D]`` of the step's
    live lanes: the embedding, the norms, every projection, the router and
    the experts run over ``N`` lanes, not ``S x K``.  ``packing = (src [N],
    back [S, K])`` says which lanes (``pack_lanes``; ``N`` is ``src``'s
    static length and must hold every live lane).  ``[S, K]`` is rebuilt
    only where a kernel needs a row's lanes side by side (``mla_chunk``'s
    attention, KDA's convolution and recurrence, ``attn_chunk``'s
    attention; a Mamba layer never does); the latents, K and V are written
    and the head reads from the packed lanes.  Without ``packing`` every
    lane keeps its place, ``N = S x K``: the widest case of the same
    trunk."""
    s, kk = tokens.shape
    li, qpos = _chunk_lanes(positions, lengths, kk)
    if packing is None:
        back = jnp.arange(s)[:, None] * kk + li
        packing = back.reshape(-1), back
    src, back = (jnp.asarray(a) for a in packing)
    valid = mla.own_places(src, back)
    eps = cfg.rms_norm_eps
    x = params["emb"][jnp.asarray(tokens).reshape(-1)[src]] \
        .astype(jnp.float32)
    new_cache, routes, picks = [], [], []
    for lp, c, (attn_kind, ffn_kind) in zip(params["layers"], cache,
                                            cfg.layers):
        h = kda.rms_norm(x, lp["norm1"], eps)
        if attn_kind == "kda":
            y, state, tail = kda.kda_chunk(
                lp["attn"], h, c["state"], c["conv"], positions, lengths,
                src, back, num_heads=cfg.kda_heads,
                head_dim=cfg.kda_head_dim, eps=eps)
            new_cache.append({"state": state, "conv": tail})
        elif attn_kind == "mamba":
            y, state, tail = mamba.mamba_chunk(
                lp["attn"], h, c["state"], c["conv"], positions, lengths,
                src, back, dt_rank=cfg.mamba_dt_rank, eps=eps)
            new_cache.append({"state": state, "conv": tail})
        elif attn_kind in ("attn", "window"):
            y, k_pool, v_pool = attn_chunk(
                lp["attn"], h, c["k"], c["v"], qpos, tables, src, back,
                **cfg.attention(attn_kind))
            new_cache.append({"k": k_pool, "v": v_pool})
        elif attn_kind == "sparse":
            y, k_pool, v_pool, ik_pool, chose = sparse_chunk(
                lp["attn"], h, c["k"], c["v"], c["ik"], qpos, tables, src,
                back, num_heads=cfg.attn_heads, kv_heads=cfg.attn_kv_heads,
                head_dim=cfg.attn_head_dim, rope=cfg.attn_rope,
                index_heads=cfg.index_heads, index_dim=cfg.index_dim,
                index_rope=cfg.index_rope, topk=cfg.index_topk, eps=eps)
            new_cache.append({"k": k_pool, "v": v_pool, "ik": ik_pool})
            picks.append(chose)
        else:
            y, pool = mla.mla_chunk(
                lp["attn"], h, c["latent"], qpos, tables, src, back,
                num_heads=cfg.mla_heads, nope=cfg.qk_nope, rope=cfg.qk_rope,
                v_dim=cfg.v_head_dim, rank=cfg.kv_rank, eps=eps,
                rope_theta=cfg.rope_theta)
            new_cache.append({"latent": pool})
        x = x + (kda.rms_norm(y, lp["post_attn"], eps) if cfg.post_norms
                 else y)
        h = kda.rms_norm(x, lp["norm2"], eps)
        f = lp["ffn"]
        if ffn_kind == "dense":
            y = moe.gated_ffn(h, f["wg"], f["wu"], f["wd"])
        else:
            idx, weights = moe.softmax_router(
                h, f["router"], cfg.top_k, cfg.routed_scale) \
                if cfg.router == "softmax" else moe.sigmoid_router(
                    h, f["router"], f["router_bias"], cfg.top_k,
                    cfg.routed_scale)
            y = moe.routed_experts(h, idx, weights, f, cfg.held,
                                   valid=valid)
            if cfg.shared_experts:
                sh = f["shared"]
                y = y + moe.gated_ffn(h, sh["wg"], sh["wu"], sh["wd"])
            routes.append(idx[back])
        x = x + (kda.rms_norm(y, lp["post_ffn"], eps) if cfg.post_norms
                 else y)
    last = x[jnp.take_along_axis(back, (lengths - 1)[:, None], axis=1)[:, 0]]
    last = kda.rms_norm(last, params["norm_f"], eps)
    # a tied head contracts the table's own columns: no transposed copy
    logits = linear.einsum("sd,vd->sv", last, params["emb"]) \
        if cfg.tie_embeddings else linear.matmul(last, params["head"])
    return logits, new_cache, routes, picks


# ------------------------------------------------------------ the engine

class Served:
    """What ``DecodeEngine(params, model=...)`` asks of a model: the
    vocabulary, a cache and the kinds of its leaves, the chunk step (with
    what it reports of itself) and the host's part of it, and which kernels
    the step will take."""

    def __init__(self, cfg, latent_dtype="float32"):
        self.cfg = cfg
        self.latent_dtype = jnp.dtype(latent_dtype)
        self.vocab_size = cfg.vocab_size
        # positions a window layer attends, 0 where no layer has a ring
        self.window = cfg.window if any(
            kind == "window" for kind, _f in cfg.layers) else 0
        # layers that select, and the positions a lane keeps in each
        self.sparse_layers = sum(kind == "sparse" for kind, _f in cfg.layers)
        self.sparse_topk = cfg.index_topk if self.sparse_layers else 0

    def init_cache(self, slots, blocks, block, chunk=1):
        """The cache for ``slots`` rows of steps of ``chunk`` lanes at
        most (a window layer's ring holds the window and a chunk)."""
        return init_cache(self.cfg, slots, blocks, block, self.latent_dtype,
                          chunk)

    def ring_bytes(self, cache):
        """Bytes of the window layers' rings in ``cache``."""
        if not self.window:
            return 0
        return sum(leaf.size * leaf.dtype.itemsize
                   for (kind, _f), c in zip(self.cfg.layers, cache)
                   if kind == "window" for leaf in c.values())

    def window_counts(self, positions, lengths):
        """What a step's lanes do in ONE window layer (numpy, on the host;
        rows feed ``lengths`` lanes from ``positions``) -> (positions
        attended: ``min(q + 1, window)`` a lane at q; positions a row's
        lanes read between them, each once: ``max(0, p - window + 1) .. p
        + n - 1``)."""
        p = np.asarray(positions, np.int64)
        n = np.asarray(lengths, np.int64)
        lane = np.arange(int(np.max(n, initial=1)))[None, :]
        at = np.minimum(p[:, None] + lane + 1, self.window)
        attended = int(np.where(lane < n[:, None], at, 0).sum())
        read = int((p + n - np.maximum(p - self.window + 1, 0)).sum())
        return attended, read

    def sparse_counts(self, positions, lengths):
        """What a step's lanes do in the sparse layers, all of them (numpy,
        on the host; rows feed ``lengths`` lanes from ``positions``) ->
        (positions the indexer scored: ``q + 1`` a lane at q; positions
        selected: ``min(q + 1, topk)``; positions the rows read, the union
        of a row's lanes' selections once a row: exact for a row that feeds
        one lane, its bound ``min(p + n, n topk)`` for one that feeds n)."""
        p = np.asarray(positions, np.int64)
        n = np.asarray(lengths, np.int64)
        lane = np.arange(int(np.max(n, initial=1)))[None, :]
        fed = lane < n[:, None]
        q = p[:, None] + lane
        scored = int(np.where(fed, q + 1, 0).sum())
        chosen = int(np.where(fed, np.minimum(q + 1, self.sparse_topk),
                              0).sum())
        read = int(np.minimum(p + n, n * self.sparse_topk).sum())
        return tuple(self.sparse_layers * x for x in (scored, chosen, read))

    def cache_kinds(self):
        return cache_kinds(self.cfg)

    def step_widths(self, slots, kk):
        """The widths the engine compiles the step at, narrowest first
        (each traced once, at warm-up)."""
        return step_widths(slots, kk)

    def pack(self, lengths, kk, width=None):
        """The host's part of a step over rows feeding ``lengths`` of
        ``kk`` lanes -> ``decode_chunk``'s two maps (src ``[N]``, back
        ``[S, K]``), as data.  ``N`` is ``width``, or the narrowest of
        ``step_widths`` that holds the live lanes."""
        src, back = pack_lanes(lengths, kk)
        if width is None:
            live = int(np.sum(lengths))
            width = next(w for w in step_widths(len(lengths), kk)
                         if w >= live)
        return src[:width], back

    def decode_chunk(self, params, tokens, positions, lengths, cache,
                     tables, src, back):
        """-> (logits, new cache, what the step reports of itself: the
        chosen experts ``[expert layers, S, K, top_k]`` int32, which the
        engine keeps on the device unread, ``DecodeEngine.step_aux``; a
        model with sparse layers reports them and the positions each sparse
        layer's lanes took, a tuple of ``[S, K, W]`` bits a sparse layer,
        as a pair: a tuple, not a stack, so that the selection kernel's
        outputs are the report and nothing copies 8 MB a layer).
        The step runs at the width of ``src``, static under ``jit``: a
        step that feeds a sixth of its lanes does not pay for all of
        them."""
        logits, cache, routes, picks = decode_chunk_report(
            params, self.cfg, tokens, positions, lengths, cache, tables,
            packing=(src, back))
        aux = jnp.stack(routes) if routes else jnp.zeros((0,), jnp.int32)
        return logits, cache, (aux, tuple(picks)) if picks else aux

    def kernel_report(self, kk, block, slots, entries=None):
        """{"kda_kernels", "kda_decline_reason", "mla_kernels",
        "mla_decline_reason", "mamba_kernels", "mamba_decline_reason",
        "attn_kernels", "attn_decline_reason", "window_kernels",
        "window_decline_reason", "sparse_kernels", "sparse_decline_reason"}
        for a step of ``slots`` rows of ``kk`` lanes over blocks of
        ``block`` positions (``entries`` of them a row's table), each from
        its kernel's own predicate (``attn``: the paged decode-attention
        kernel under the ``"attn"`` layers; ``window``: its windowed form
        over the rings, or the paged one where the window layers have no
        window; ``sparse``: the indexer, selection and attention kernels,
        ops/pallas/dsa.py; ``mamba``: at every width the step is compiled
        at); False and no reason for a kind of layer the model does not
        have."""
        from paddle_tpu.ops.pallas import decode_attention
        from paddle_tpu.ops.pallas import dsa as dsa_kernels
        from paddle_tpu.ops.pallas import kda as kda_kernel
        from paddle_tpu.ops.pallas import mamba as mamba_kernel
        from paddle_tpu.ops.pallas import mla as mla_kernel
        cfg = self.cfg
        kinds = {kind for kind, _f in cfg.layers}
        why = {"kda": kda_kernel.decline_reason(
                   kk, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)
               if "kda" in kinds else None,
               "mla": mla_kernel.decline_reason(
                   kk, cfg.mla_heads, mla.pool_width(cfg.latent_width),
                   cfg.kv_rank, block, self.latent_dtype)
               if "mla" in kinds else None,
               "mamba": next(filter(None, (
                   mamba_kernel.decline_reason(
                       width, slots, cfg.mamba_inner, cfg.mamba_state)
                   for width in step_widths(slots, kk))), None)
               if "mamba" in kinds else None,
               "attn": decode_attention.decline_reason(
                   cfg.attn_heads, cfg.attn_heads * cfg.attn_head_dim,
                   cfg.attn_kv_heads * cfg.attn_head_dim, block, paged=True,
                   chunk=kk)
               if "attn" in kinds else None,
               "window": (decode_attention.window_decline_reason(
                   cfg.window_heads, cfg.window_heads * cfg.attn_head_dim,
                   cfg.attn_kv_heads * cfg.attn_head_dim, block,
                   entries or -(-(cfg.window + kk) // block), kk)
                   if cfg.window else decode_attention.decline_reason(
                       cfg.window_heads, cfg.window_heads * cfg.attn_head_dim,
                       cfg.attn_kv_heads * cfg.attn_head_dim, block,
                       paged=True, chunk=kk))
               if "window" in kinds else None,
               "sparse": dsa_kernels.decline_reason(
                   cfg.attn_heads, cfg.attn_heads * cfg.attn_head_dim,
                   cfg.attn_kv_heads * cfg.attn_head_dim, block,
                   entries or 1, kk, cfg.index_heads)
               if "sparse" in kinds else None}
        return {**{k + "_kernels": k in kinds and why[k] is None
                   for k in why},
                **{k + "_decline_reason": why[k] for k in why}}
