"""Plain reference forward pass of SmallThinker (``smallthinker_*``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one sequence at a time, every layer over the whole sequence under its own
mask, every expert computed densely over every token and masked by the
routing, no cache, no page, no kernel, no batching; it imports nothing of
the serving path.  The served program (``inference/v2``) is held to it on
logits.

One layer (x in R^e the residual stream, layer l)::

    a   = rmsnorm(x) * g_in                          eps 1e-6
    s   = float32(a) Wr            [e, E]            the router reads the
                                                     ATTENTION block's input
    p   = softmax(s) over all E;  X = the top_k largest;
    w_e = p_e / sum_X p            (norm_topk_prob)
    q   = a Wq -> [H, D];  k = a Wk -> [K, D];  v = a Wv -> [K, D]
    rope_layout[l] == 1:  q, k = rope(q, k, pos)     theta, all D dims,
                          else as projected          absolute position
    o_i = softmax_j(q_i . k_j / sqrt(D)) v_j  over j <= i, and
          i - j < window where sliding_window_layout[l] == 1;
          head n uses KV head n // (H / K)
    h   = x + concat_n(o_n) Wo
    b   = rmsnorm(h) * g_post
    y   = sum_{e in X} w_e Wd_e (relu(Wg_e b) * (Wu_e b))     ReGLU
    x'  = h + y

then the final rmsnorm and the untied head.

Departures from the source, all of them:

* ASSUMED, the source's config has no key for them: the router reads the
  NORMED attention input (described as "router placed before attention");
  the gate's activation is ReLU ("sparse ReGLU", no ``hidden_act``); no
  attention bias and no Q/K norm; rope pairs are interleaved
  ``(x[2i], x[2i+1])``.
* the two per-layer lists are read as ONE list of kinds ("full": global,
  no rope; "window": windowed, roped), as ``models/smallthinker.py`` holds
  them to agree.
* EXPERTS HELD: ``sizes["experts_first"]`` / the experts' stack say which
  experts are here; with every expert held (the family's serving cut) it
  is the whole layer.

``sizes`` are plain numbers and tuples: ``eps head_dim kinds window
rope_theta top_k norm_topk_prob experts_first`` and, so that a test can
plant each fault the comparison has to catch, ``roped`` (the kinds under
rope: ``("window",)``), ``act`` ("relu") and ``router_reads`` ("mixer";
"ffn": the post-attention norm's output).  ``params`` is the unboxed tree
of ``models/smallthinker.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def rms_norm(x, gain, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(F32)


def rope(x, positions, theta):
    """x [T, H, D]: every dim rotated over interleaved pairs."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = positions.astype(F32)[:, None] * freqs            # [T, D/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(a, ap, kind, sizes):
    """a [T, e] of ONE sequence, causal over its own tokens (and the
    window, on a window layer)."""
    T, D = a.shape[0], sizes["head_dim"]
    pos = jnp.arange(T)
    # head n of a projection = its columns n*D .. n*D + D - 1
    q = (a @ ap["wq"].astype(F32)).reshape(T, -1, D)
    k = (a @ ap["wk"].astype(F32)).reshape(T, -1, D)
    v = (a @ ap["wv"].astype(F32)).reshape(T, -1, D)
    if kind in sizes["roped"]:
        q, k = (rope(q, pos, sizes["rope_theta"]),
                rope(k, pos, sizes["rope_theta"]))
    H, K = q.shape[1], k.shape[1]
    qg = q.reshape(T, K, H // K, D)                 # head n = k * G + g
    scores = jnp.einsum("tkgd,skd->kgts", qg, k) / jnp.sqrt(F32(D))
    keep = pos[None, :] <= pos[:, None]
    if kind == "window":
        keep &= pos[:, None] - pos[None, :] < sizes["window"]
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgts,skd->tkgd", probs, v)
    return out.reshape(T, H * D) @ ap["wo"].astype(F32)


def route(a, router, sizes):
    """(experts [T, k], weights [T, k]) over ALL experts."""
    probs = jax.nn.softmax(a @ router.astype(F32), axis=-1)
    top, experts = jax.lax.top_k(probs, sizes["top_k"])
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return experts, top


def experts_ffn(b, experts, weights, stack, sizes):
    """Every held expert over every token, masked by the routing;
    returns (y [T, e], the chosen experts that are held here, a token
    [T])."""
    act = ACTS[sizes.get("act", "relu")]
    first = sizes.get("experts_first", 0)
    held = stack["wg"].shape[0]
    y = jnp.zeros_like(b)
    for i in range(held):
        w = jnp.sum(jnp.where(experts == first + i, weights, 0.0), axis=-1)
        wg, wu, wd = (stack[n][i].astype(F32) for n in ("wg", "wu", "wd"))
        y = y + w[:, None] * ((act(b @ wg.T) * (b @ wu.T)) @ wd)
    here = jnp.sum((experts >= first) & (experts < first + held), axis=-1)
    return y, here


def layers_of(params):
    """The layers in order: the periods' layers, then the tail."""
    out = []
    stacks = params.get("periods", {})
    if stacks:
        for p in range(jax.tree.leaves(stacks)[0].shape[0]):
            out += [jax.tree.map(lambda x, p=p: x[p], stacks[f"l{j}"])
                    for j in range(len(stacks))]
    out += [params["tail"][f"l{i}"]
            for i in range(len(params.get("tail", {})))]
    return out


def layer(x, lp, kind, stack, sizes):
    """One layer over x [T, e]; returns (x, held pairs a token [T])."""
    eps = sizes["eps"]
    a = rms_norm(x, lp["norm1"]["scale"], eps)
    before = sizes.get("router_reads", "mixer") == "mixer"
    if before:                  # routed from the attention block's input
        experts, weights = route(a, lp["moe"]["router"], sizes)
    h = x + attention(a, lp["attn"], kind, sizes)
    b = rms_norm(h, lp["norm2"]["scale"], eps)
    if not before:
        experts, weights = route(b, lp["moe"]["router"], sizes)
    y, here = experts_ffn(b, experts, weights, stack, sizes)
    return h + y, here


def forward(params, token_ids, sizes):
    """token_ids [T] of one sequence -> (logits [T, V] float32, pairs that
    fell to held experts, a layer and token [layers, T])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"].astype(F32)[token_ids]
        pairs = []
        for i, (lp, kind) in enumerate(zip(layers_of(params),
                                           sizes["kinds"])):
            stack = {n: params["experts"][n][i] for n in ("wg", "wu", "wd")}
            x, here = layer(x, lp, kind, stack, sizes)
            pairs.append(here)
        x = rms_norm(x, params["final_norm"]["scale"], sizes["eps"])
        return x @ params["lm_head"].astype(F32), jnp.stack(pairs)


def sizes_of(cfg) -> dict:
    """``sizes`` from a ``TransformerConfig`` (plain attribute reads)."""
    kinds = tuple(cfg.layer_kinds)
    return dict(
        eps=cfg.norm_eps, head_dim=cfg.dims_per_head, kinds=kinds,
        window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        roped=tuple(k for k in dict.fromkeys(kinds)
                    if k not in cfg.nope_kinds),
        top_k=cfg.moe_top_k, norm_topk_prob=cfg.norm_topk_prob,
        act=cfg.expert_act, router_reads=cfg.router_reads,
        experts_first=cfg.experts_first)
