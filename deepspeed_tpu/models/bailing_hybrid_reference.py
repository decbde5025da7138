"""Plain reference of Ling-3.0 (``models/bailing_hybrid.py``): what the
served program is held to.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
one sequence at a time, every layer over the whole sequence, from a zero
state, the Kimi-delta recurrence token by token (a plain ``lax.scan`` over
positions: no chunk, no matrix form), latent attention EXPANDED (keys and
values by head from the latent, a causal mask), the grouped router with
``jax.lax.top_k``, the held share of the experts one expert at a time: no
cache, no page, no slot, no kernel, no batching; it imports nothing of the
program.  For a sequence ``x`` ``[T, e]`` entering a layer::

    every layer:  h = x + mixer(rmsnorm(x) g_1)
                  out = h + ffn(rmsnorm(h) g_2)
    KDA mixer:    [q ; k ; v] = x W_qkv
                  c_t = silu(sum_j w_conv[j] c_{t-(K-1)+j}) a channel
                  q_h = l2norm(q_h) / sqrt(d), k_h = l2norm(k_h)
                  g = lower * sigmoid(exp(A_log_h) (W_f x + dt_bias))  [H, d]
                  beta_h = sigmoid((W_beta x)_h)
                  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                        + beta_t k_t v_t^T            (a head, [d, d])
                  o_t = S_t^T q_t
                  y_h = rmsnorm(o_h) g_o * sigmoid((W_g x)_h);  W_o concat(y)
    latent mixer: q = x W_q (direct), [c ; k_r] = x W_kv_a, c = rmsnorm(c)
                  k = [c W_k ; rope(k_r)], v = c W_v, q = [q_n ; rope(q_r)]
                  softmax(q k^T / sqrt(d_n + d_r)) under the causal mask; W_o
    dense ffn:    (silu(h W_g) * (h W_i)) W_o
    routed ffn:   s = sigmoid(h W_r); c = s + bias; a group (E / n_group
                  neighbouring experts) scores the sum of its two largest
                  c; the topk_group best groups are kept; the top_k largest
                  c among their experts are chosen; w_i = scale * s_i /
                  sum_chosen s_j; sum_i w_i E_i(h) over the chosen experts
                  HELD here, plus the shared expert
    then the final norm and the head.

``l2norm(a) = a / sqrt(sum(a^2) + 1e-6)``; rope pairs are interleaved
``(x[2i], x[2i+1])``.  It reads the served bfloat16 weights and upcasts
ONE LAYER AT A TIME (a jitted layer function, the layers iterated in
python), so that the whole model's float32 copy never exists beside the
served model on the chip; the head is taken in blocks of rows.

``sizes``: ``eps kinds first_k_dense conv heads dk dv lower rope_theta
kv_lora_rank qk_nope_head_dim qk_rope_head_dim top_k n_group topk_group
routed_scaling_factor norm_topk_prob experts_first`` and, for the probe's
controls (each plants ONE fault that the comparison has to see), ``decay``
("head": one decay a head, the mean of its channels' ``g``, for one a
channel), ``groups`` (False: the top-k over all experts), ``bias`` (False:
``c = s``), ``weights_from`` ("c": the weights from ``c``), ``latent_rope``
(False: no rope on the latent layer).  ``state_precision``: a dtype the
matrix state is rounded through after every step (bfloat16: the nearest
precision below the configuration's).  ``weight_precision``: a dtype every
weight matrix is rounded through first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: rows of the head taken at once ([rows, vocabulary] float32)
HEAD_ROWS = 512
#: the leaves the program holds and reads in float32 whatever its dtype
FLOAT32_LEAVES = ("router", "router_bias", "A_log", "dt_bias")


def sizes_of(cfg, **controls) -> dict:
    """``sizes`` from the program's configuration (plain attribute
    reads); ``controls``: the module docstring's."""
    return dict(dict(
        eps=cfg.norm_eps, kinds=tuple(cfg.layer_kinds),
        first_k_dense=cfg.first_k_dense, conv=cfg.delta_conv,
        heads=cfg.delta_heads, dk=cfg.delta_key_dim,
        dv=cfg.delta_value_dim, lower=cfg.kda_lower_bound,
        rope_theta=cfg.rope_theta, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, top_k=cfg.moe_top_k,
        n_group=cfg.router_groups, topk_group=cfg.router_topk_groups,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
        experts_first=cfg.experts_first), **controls)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * gain


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                             + jnp.asarray(1e-6, x.dtype))


def swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def rope(x, positions, theta):
    """x [T, H, d] rotated over interleaved pairs; positions [T]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(x, ap, sizes):
    """x [T, e] of ONE sequence -> [T, e]: expanded latent attention."""
    T = x.shape[0]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rkv, eps, pos = sizes["kv_lora_rank"], sizes["eps"], jnp.arange(T)
    q = jnp.einsum("te,ehd->thd", x, ap["wq"])
    ckr = x @ ap["wkv_a"]
    c = rms_norm(ckr[:, :rkv], ap["kv_norm"]["scale"], eps)
    q_r, k_r = q[..., dn:], ckr[:, None, rkv:]
    if sizes.get("latent_rope", True):
        q_r = rope(q_r, pos, sizes["rope_theta"])
        k_r = rope(k_r, pos, sizes["rope_theta"])
    k_n = jnp.einsum("tr,rhd->thd", c, ap["wkv_b_k"])
    v = jnp.einsum("tr,rhd->thd", c, ap["wkv_b_v"])
    scores = (jnp.einsum("thd,shd->hts", q[..., :dn], k_n)
              + jnp.einsum("thd,sd->hts", q_r, k_r[:, 0])).astype(
                  jnp.float32) * float(dn + dr) ** -0.5
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs.astype(x.dtype), v)
    return jnp.einsum("thd,hde->te", out, ap["wo"])


def kda_mixer(x, mp, sizes, state_precision):
    """x [T, e] from a zero state -> [T, e]."""
    T = x.shape[0]
    H, dk, dv, K = (sizes[n] for n in ("heads", "dk", "dv", "conv"))
    qkv = x @ mp["w_qkv"]
    xp = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), x.dtype), qkv])
    conv = sum(xp[j:j + T] * mp["conv_w"][j] for j in range(K))
    qkv = jax.nn.silu(conv)
    q = l2_norm(qkv[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = l2_norm(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    f = (x @ mp["w_f"].T + mp["dt_bias"]).reshape(T, H, dk)
    g = sizes["lower"] * jax.nn.sigmoid(
        jnp.exp(mp["A_log"])[None, :, None] * f)
    if sizes.get("decay", "channel") == "head":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    bg = x @ mp["w_bg"].T                                   # [T, 2 H]
    beta, gate = jax.nn.sigmoid(bg[:, :H]), jax.nn.sigmoid(bg[:, H:])

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp     # [H,dk] [H,dk] [H,dv] [H,dk] [H]
        S = S * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S))
        S = S + k_t[:, :, None] * u[:, None, :]
        if state_precision is not None:
            # an explicit rounding: a cast there and back is one the
            # chip's compiler may drop (excess precision is allowed)
            fi = jnp.finfo(state_precision)
            S = jax.lax.reduce_precision(S, fi.nexp, fi.nmant)
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), x.dtype),
                        (q, k, v, g, beta))
    y = rms_norm(o, mp["o_norm"]["scale"], sizes["eps"]) * gate[..., None]
    return y.reshape(T, -1) @ mp["w_out"]


def route(x, mp, sizes):
    """(experts [T, k], weights [T, k]) over ALL experts."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32)
                            @ mp["router"].astype(jnp.float32))
    choice = scores + (mp["router_bias"].astype(jnp.float32)
                       if sizes.get("bias", True) else 0.0)
    T, E = scores.shape
    masked = choice
    if sizes.get("groups", True):
        groups = choice.reshape(T, sizes["n_group"], -1)
        score = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)    # [T, n_group]
        kept = jax.lax.top_k(score, sizes["topk_group"])[1]
        mask = jnp.any(kept[:, :, None] == jnp.arange(sizes["n_group"]), 1)
        masked = jnp.where(mask[:, :, None], groups, -jnp.inf).reshape(T, E)
    experts = jax.lax.top_k(masked, sizes["top_k"])[1]
    top = jnp.take_along_axis(
        choice if sizes.get("weights_from", "s") == "c" else scores,
        experts, axis=-1)
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return experts, top * sizes["routed_scaling_factor"]


def routed_ffn(x, mp, experts_w, sizes):
    """The held experts' partial sum + the shared expert; also, a token,
    the chosen experts that are held here."""
    experts, weights = route(x, mp, sizes)
    first, held = sizes.get("experts_first", 0), experts_w["wg"].shape[0]

    def one(y, i):
        w = jnp.sum(jnp.where(experts == first + i, weights, 0.0),
                    axis=-1).astype(x.dtype)                # [T]
        wg, wu, wd = (experts_w[n][i] for n in ("wg", "wu", "wd"))
        return y + w[:, None] * (
            (jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if "shared" in mp:
        y = y + swiglu(x, mp["shared"])
    here = (experts >= first) & (experts < first + held)
    return y, jnp.sum(here, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "kind", "sizes_key", "precision", "weight_precision",
    "state_precision"))
def _layer(x, lp, experts_w, kind, sizes_key, precision, weight_precision,
           state_precision):
    """One layer over the whole sequence, its weights cast here; returns
    (x, held pairs a token or None)."""
    sizes = dict(sizes_key)
    eps = sizes["eps"]

    def cast(path, a):
        if path[-1].key in FLOAT32_LEAVES:
            return a
        if weight_precision is not None and a.ndim >= 2:
            a = a.astype(weight_precision)
        return a.astype(precision)

    lp, experts_w = jax.tree_util.tree_map_with_path(cast, (lp, experts_w))
    h = rms_norm(x, lp["norm1"]["scale"], eps)
    if kind == "kda":
        x = x + kda_mixer(h, lp["mixer"], sizes, state_precision)
    else:
        x = x + latent_attention(h, lp["attn"], sizes)
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    if "moe" in lp:
        f, pairs = routed_ffn(h, lp["moe"], experts_w, sizes)
        return x + f, pairs
    return x + swiglu(h, lp["mlp"]), None


@functools.partial(jax.jit, static_argnames=(
    "eps", "precision", "weight_precision"))
def _head(x, gain, lm_head, eps, precision, weight_precision):
    x = rms_norm(x, gain.astype(precision), eps)
    if weight_precision is not None:
        lm_head = lm_head.astype(weight_precision)
    return (x @ lm_head.astype(precision)).astype(jnp.float32)


def layers_of(params, n_layers):
    """(layer's tree, its held experts or None) in order
    (``models/bailing_hybrid.py``'s tree, read as data)."""
    dense, runs = params.get("dense_layers", {}), params.get("runs", {})
    trees = [dense[f"l{i}"] for i in range(len(dense))]
    for j in range(len(runs)):
        stack = runs[f"r{j}"]
        trees += [jax.tree.map(lambda a, m=m: a[m], stack)
                  for m in range(jax.tree.leaves(stack)[0].shape[0])]
    assert len(trees) == n_layers, (len(trees), n_layers)
    routed = 0
    for lp in trees:
        ex = None
        if "moe" in lp:
            ex = jax.tree.map(lambda a, r=routed: a[r], params["experts"])
            routed += 1
        yield lp, ex


def forward(params, token_ids, sizes, precision=jnp.float32,
            weight_precision=None, state_precision=None):
    """token_ids [T] of one sequence, ``params`` the program's unboxed
    tree (read as data) -> (logits [T, V] float32, the chosen experts held
    here a routed layer and token [routed layers, T])."""
    key = tuple(sorted(sizes.items()))
    pairs = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(token_ids)].astype(precision)
        for (lp, ex), kind in zip(layers_of(params, len(sizes["kinds"])),
                                  sizes["kinds"]):
            x, here = _layer(x, lp, ex, kind, key, precision,
                             weight_precision, state_precision)
            if here is not None:
                pairs.append(here)
        logits = jnp.concatenate([
            _head(x[lo:lo + HEAD_ROWS], params["final_norm"]["scale"],
                  params["lm_head"], sizes["eps"], precision,
                  weight_precision)
            for lo in range(0, x.shape[0], HEAD_ROWS)])
    return logits, (jnp.stack(pairs) if pairs
                    else jnp.zeros((0, x.shape[0]), jnp.int32))
