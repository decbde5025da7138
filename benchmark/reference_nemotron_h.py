"""The benchmark's own copy of the plain reference of Nemotron-H: what the
cell ``serve.reason-ssd-closed256`` is held to on the chip.

A copy, so that a change to ``deepspeed_tpu/models/nemotron_h_reference.py``
cannot move what the benchmark judges by (``tests/benchmark/
test_benchmark_nemotron_h.py`` holds the two to the same logits).  Float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, one
sequence at a time, every layer over the whole sequence from a zero state,
ONE LAYER AT A TIME (a jitted layer function, the layers iterated in python,
a routed layer's held experts one after another, the head in blocks of
:data:`HEAD_ROWS` rows), so that it fits beside the engine on the chip: no
cache, no page, no slot, no kernel, no batching; it imports nothing of the
program.  For a sequence ``u`` ``[T, e]`` entering a layer: ``x = u +
sub(rmsnorm(u) * gain)``, ONE norm and ONE residual, ``sub`` by the layer's
kind::

    "ssd" (M)   [z | xBC | dt] = h W_in            (d | d + 2 G N | H)
                xBC_t = silu(b + sum_k w[k] xBC_{t-(K-1)+k})
                [x | B | C] = xBC  (x [H, P]; B, C [G, N]; head h reads
                      group h // (H / G))
                dt = softplus(dt + dt_bias);  a = exp(dt A), A = -exp(A_log)
                S_t = a_t S_{t-1} + (dt_t x_t) (x) B_t     (a plain
                y_t = S_t C_t + D x_t                       lax.scan over t)
                y = y silu(z);  y = rmsnorm over each group of d / G
                      channels, times a gain over all d;  out = y W_out
    "ffn" (E)   s = sigmoid(h W_r) (float32);  the top_k largest of s +
                bias are chosen;  w_i = scaling s_i / (sum chosen s + 1e-20)
                out = sum_i w_i W_down,i relu(W_up,i h)^2 over the chosen
                      experts HELD + the shared expert, the same form
    "full" (*)  q, k, v by head, NO rope, no bias; causal softmax(q k^T /
                sqrt(dh)), the query heads of a group over their one K/V
                head; Wo
    then the final norm and the untied head.

``sizes``: ``eps head_dim kinds conv heads head_p groups state top_k
routed_scaling_factor norm_topk_prob experts_first`` and, for the probe's
controls (each plants ONE fault that the comparison has to see): ``act``
("relu"), ``bc_groups`` (False: every head reads group 0), ``norm_groups``
(False: the norm over the whole width), ``gate_first`` (False: the norm
before the gate), ``bias`` (False: the router chooses by ``s``),
``weights_from`` ("c": the weights from the biased scores), ``rope`` (a
base: a rope on the attention layers), ``skip`` (False: ``D = 0``).
``state_precision``: a dtype the recurrent state is rounded through after
every step.  ``routing`` ``[T, routed layers, top_k]``: the SERVED experts,
weighed in place of the router's own choice (``builders/
serve_nemotron_h.py``: a near-tie of 128 scores falls either way under
bfloat16 and says nothing of the arithmetic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: leaves that stay float32 whatever ``weight_precision`` says
FLOAT32_LEAVES = ("router", "router_bias", "A_log", "D", "dt_bias")
#: rows of the final norm and head a call takes (the logits of a long
#: sequence at once are the largest array of the forward pass)
HEAD_ROWS = 512


def sizes_of(cfg, **controls) -> dict:
    """``sizes`` from the program's configuration (plain attribute reads);
    ``controls``: the module docstring lists them."""
    return dict(dict(
        eps=cfg.norm_eps, head_dim=cfg.dims_per_head,
        kinds=tuple(cfg.layer_kinds), conv=cfg.ssm_conv,
        heads=cfg.ssm_heads, head_p=cfg.ssm_head_dim,
        groups=cfg.ssm_groups, state=cfg.ssm_state_dim,
        top_k=cfg.moe_top_k,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
        experts_first=cfg.experts_first), **controls)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * gain


def relu2_mlp(x, w_up, w_down, sizes):
    """``W_down act(W_up x)``, ``w_up`` ``[e, F]``, ``w_down`` ``[F, e]``."""
    h = jax.nn.relu(x @ w_up)
    return (h if sizes.get("act", "relu2") == "relu" else h * h) @ w_down


def attention(h, ap, sizes):
    """h [T, e] -> [T, e]: causal softmax attention, no rope (``rope``: a
    control's base)."""
    T, d = h.shape[0], sizes["head_dim"]
    q = (h @ ap["wq"]).reshape(T, -1, d)
    k = (h @ ap["wk"]).reshape(T, -1, d)
    v = (h @ ap["wv"]).reshape(T, -1, d)
    if sizes.get("rope"):
        inv = sizes["rope"] ** (-jnp.arange(0, d, 2, dtype=h.dtype) / d)
        ang = jnp.arange(T, dtype=h.dtype)[:, None] * inv[None, :]
        sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]

        def rotate(a):
            a1, a2 = a[..., :d // 2], a[..., d // 2:]
            return jnp.concatenate([a1 * cos - a2 * sin,
                                    a2 * cos + a1 * sin], -1)

        q, k = rotate(q), rotate(k)
    K = k.shape[1]
    q = q.reshape(T, K, -1, d)                              # [T, K, G, d]
    s = jnp.einsum("tkgd,ukd->kgtu", q, k).astype(jnp.float32) * d ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p.astype(h.dtype), v)
    return o.reshape(T, -1) @ ap["wo"]


def ssd_mixer(u, mp, sizes, state_precision):
    """u [T, e] from a zero state -> [T, e]."""
    T = u.shape[0]
    H, P, G, N, K = (sizes[k] for k in ("heads", "head_p", "groups",
                                        "state", "conv"))
    d, gn = H * P, G * N
    zxd = u @ mp["w_in"]
    z, xbc, dt = zxd[:, :d], zxd[:, d:2 * d + 2 * gn], zxd[:, 2 * d + 2 * gn:]
    xp = jnp.concatenate([jnp.zeros((K - 1, d + 2 * gn), u.dtype), xbc])
    conv = mp["conv_b"]
    for k in range(K):                                      # shifted products
        conv = conv + xp[k:k + T] * mp["conv_w"][k]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d].reshape(T, H, P)
    B = xbc[:, d:d + gn].reshape(T, G, N)
    C = xbc[:, d + gn:].reshape(T, G, N)
    if not sizes.get("bc_groups", True):
        B, C = B[:, :1].repeat(G, 1), C[:, :1].repeat(G, 1)
    B, C = (jnp.repeat(a, H // G, axis=1) for a in (B, C))  # [T, H, N]
    dt = jax.nn.softplus(dt + mp["dt_bias"])                # [T, H]
    A = -jnp.exp(mp["A_log"])
    D = mp["D"] if sizes.get("skip", True) else jnp.zeros_like(mp["D"])

    def step(S, inp):
        dt_t, x_t, b_t, c_t = inp           # [H] [H, P] [H, N] [H, N]
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if state_precision is not None:
            # an explicit rounding: a cast there and back is one the
            # chip's compiler may drop (excess precision is allowed)
            fi = jnp.finfo(state_precision)
            S = jax.lax.reduce_precision(S, fi.nexp, fi.nmant)
        return S, jnp.einsum("hpn,hn->hp", S, c_t) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), u.dtype), (dt, x, B, C))
    y, gate = y.reshape(T, d), jax.nn.silu(z)
    groups = G if sizes.get("norm_groups", True) else 1

    def norm(a):
        return rms_norm(a.reshape(T, groups, -1), 1.0,
                        sizes["eps"]).reshape(T, d)

    y = norm(y * gate) if sizes.get("gate_first", True) else norm(y) * gate
    return (y * mp["norm"]["scale"]) @ mp["w_out"]


def route(x, mp, sizes, forced=None):
    """(experts [T, k], weights [T, k], the router's own choice [T, k]) over
    ALL experts; ``forced``: the experts to weigh instead of that choice."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32)
                            @ mp["router"].astype(jnp.float32))
    choice = scores + (mp["router_bias"].astype(jnp.float32)
                       if sizes.get("bias", True) else 0.0)
    free = jax.lax.top_k(choice, sizes["top_k"])[1]
    experts = free if forced is None else forced
    top = jnp.take_along_axis(
        choice if sizes.get("weights_from", "s") == "c" else scores,
        experts, axis=-1)
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return experts, top * sizes["routed_scaling_factor"], free


def routed_ffn(x, mp, experts_w, sizes, forced=None):
    """The held experts' partial sum + the shared expert; also, a token,
    the experts the router chose that are held here, and whether
    ``forced`` (the experts multiplied instead, where given) is another
    set than the router's."""
    experts, weights, free = route(x, mp, sizes, forced)
    first, held = sizes.get("experts_first", 0), experts_w["wu"].shape[0]

    def one(y, i):
        w = jnp.sum(jnp.where(experts == first + i, weights, 0.0),
                    axis=-1).astype(x.dtype)                # [T]
        return y + w[:, None] * relu2_mlp(
            x, experts_w["wu"][i].T, experts_w["wd"][i], sizes), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if "shared" in mp:
        y = y + relu2_mlp(x, mp["shared"]["wi"], mp["shared"]["wo"], sizes)
    here = (free >= first) & (free < first + held)
    off = jnp.any(jnp.sort(free, -1) != jnp.sort(experts, -1), -1)
    return y, jnp.sum(here, axis=-1).astype(jnp.int32), off


@functools.partial(jax.jit, static_argnames=(
    "kind", "sizes_key", "precision", "state_precision"))
def _layer(x, lp, experts_w, forced, kind, sizes_key, precision,
           state_precision):
    """One layer over the whole sequence, its weights (as :func:`_stored`
    left them) cast here; returns (x, held pairs a token or None, ``forced``
    is off the router's choice a token or None)."""
    sizes = dict(sizes_key)
    lp, experts_w = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in FLOAT32_LEAVES
        else a.astype(precision), (lp, experts_w))
    h = rms_norm(x, lp["norm1"]["scale"], sizes["eps"])
    if kind == "ssd":
        return x + ssd_mixer(h, lp["mixer"], sizes, state_precision), \
            None, None
    if kind == "full":
        return x + attention(h, lp["attn"], sizes), None, None
    f, pairs, off = routed_ffn(h, lp["moe"], experts_w, sizes, forced)
    return x + f, pairs, off


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, gain, lm_head, eps, precision):
    x = rms_norm(x, gain.astype(precision), eps)
    return (x @ lm_head.astype(precision)).astype(jnp.float32)


def _stored(tree, weight_precision):
    """``tree``'s matrices as a store of ``weight_precision`` would hold
    them (None: as they are), each rounded in a computation of its OWN:
    inside the jitted layer a cast there and back is one the chip's compiler
    drops (on the chip float8 weights read the sound readings to the last
    digit: PERF.md section 6, PR 54)."""
    if weight_precision is None:
        return tree
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in FLOAT32_LEAVES or a.ndim < 2
        else a.astype(weight_precision), tree)


def layers_of(params, kinds):
    """(layer's tree, its held experts or None) in order
    (``models/nemotron_h.py``'s tree, read as data)."""
    stacks, tail = params.get("periods", {}), params.get("tail", {})
    periods = jax.tree.leaves(stacks)[0].shape[0] if stacks else 0
    trees = [jax.tree.map(lambda a, p=p: a[p], stacks[f"l{j}"])
             for p in range(periods) for j in range(len(stacks))]
    trees += [tail[f"l{n}"] for n in range(len(tail))]
    assert len(trees) == len(kinds), (len(trees), len(kinds))
    routed = 0
    for lp in trees:
        ex = None
        if "moe" in lp:
            ex = jax.tree.map(lambda a, r=routed: a[r], params["experts"])
            routed += 1
        yield lp, ex


def compile_ahead(params, sizes, lengths, precision=jnp.float32) -> int:
    """Lower and compile, without running anything, what :func:`forward`
    under a ``routing`` will call for sequences of ``lengths`` tokens: one
    layer function a (length, kind) and the head's blocks.  Nothing is
    kept: with JAX's persistent compile cache on, the calls that follow
    load what this compiled; without one it is time lost and nothing else.
    Returns the count."""
    key = tuple(sorted(sizes.items()))
    kinds, k = sizes["kinds"], sizes["top_k"]
    trees = jax.eval_shape(lambda p: list(layers_of(p, kinds)), params)
    gain, head = params["final_norm"]["scale"], params["lm_head"]
    done = set()
    with jax.default_matmul_precision("highest"):
        for n in lengths:
            x = jax.ShapeDtypeStruct((n, head.shape[0]), precision)
            for (lp, ex), kind in zip(trees, kinds):
                if (n, kind) in done:
                    continue
                done.add((n, kind))
                forced = None if ex is None else \
                    jax.ShapeDtypeStruct((n, k), jnp.int32)
                _layer.lower(x, lp, ex, forced, kind, key, precision,
                             None).compile()
            for rows in {min(HEAD_ROWS, n - lo)
                         for lo in range(0, n, HEAD_ROWS)} - done:
                done.add(rows)
                _head.lower(jax.ShapeDtypeStruct((rows, head.shape[0]),
                                                 precision), gain, head,
                            sizes["eps"], precision).compile()
    return len(done)


def forward(params, token_ids, sizes, precision=jnp.float32,
            weight_precision=None, state_precision=None, routing=None):
    """token_ids [T] of one sequence, ``params`` the program's unboxed
    tree (read as data) -> (logits [T, V] float32, the router's chosen
    experts held here a routed layer and token [routed layers, T], whether
    ``routing`` [T, routed layers, top_k] names another set than the
    router's there, same shape; all False without ``routing``)."""
    key = tuple(sorted(sizes.items()))
    pairs, offs = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(token_ids)].astype(precision)
        for (lp, ex), kind in zip(layers_of(params, sizes["kinds"]),
                                  sizes["kinds"]):
            forced = None
            if routing is not None and ex is not None:
                forced = jnp.asarray(routing[:, len(pairs)], jnp.int32)
            lp, ex = _stored((lp, ex), weight_precision)
            x, here, off = _layer(x, lp, ex, forced, kind, key, precision,
                                  state_precision)
            if here is not None:
                pairs.append(here)
                offs.append(off)
        head = _stored({"lm_head": params["lm_head"]},
                       weight_precision)["lm_head"]
        logits = jnp.concatenate([
            _head(x[lo:lo + HEAD_ROWS], params["final_norm"]["scale"], head,
                  sizes["eps"], precision)
            for lo in range(0, x.shape[0], HEAD_ROWS)])
    if not pairs:
        return logits, jnp.zeros((0, x.shape[0]), jnp.int32), \
            jnp.zeros((0, x.shape[0]), bool)
    return logits, jnp.stack(pairs), jnp.stack(offs)
