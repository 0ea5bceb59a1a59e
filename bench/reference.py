"""Plain reference of one training cell: the model, its loss, its gradients
and AdamW, in float32 at ``highest`` matmul precision.

Written from the paper and the configuration alone; it imports nothing of
the program and takes nothing the program made (it starts from the
benchmark's own seeded weights, ``weights.init_params``).

The model is a pre-norm dense decoder: token plus learned position
embeddings; per layer LayerNorm -> causal multi-head attention (grouped
keys and values) -> residual, LayerNorm -> GELU (tanh) MLP -> residual; a
final LayerNorm and the LM head (the embedding, transposed, when tied);
mean next-token cross-entropy.  LayerNorm is ``(x - mu) / sqrt(var +
1e-5) * (1 + scale) + bias``.  Linears carry no bias.

Every linear ``y = x @ w`` quantizes the operands of its three matmuls as
the recipe states (the paper's App. A, Eq. 1-7): a scale ``amax / Q_max``
per group, round to nearest even on the format's grid, rescale.  Groups
are taken relative to each matmul's own reduction axis: ``token`` is one
scale per row of the left operand (per column of the right one), ``block``
one per 128 along the reduction axis, ``tile`` one per 128 x 128 tile.
Gradients pass the quantizers straight through.

``lower=True`` gives the control: every operand computed in the next
precision below the one the recipe states: bfloat16 in FP8 per token (E4M3
for activations and weights, E5M2 for gradients), FP8 in FP4 at the same
granularity.  FP4 has no step below and stays.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["RECIPES", "Ref", "run_reference"]

_ML = {"fp4_e2m1": (jnp.float4_e2m1fn, 6.0),
       "fp8_e4m3": (jnp.float8_e4m3fn, 448.0),
       "fp8_e5m2": (jnp.float8_e5m2, 57344.0)}
_ROLES = ("fwd_x", "fwd_w", "dgrad_g", "dgrad_w", "wgrad_x", "wgrad_g")

# role -> (format, granularity) or None for bfloat16, per linear class
_FP8 = {"fwd_x": ("fp8_e4m3", "token"), "fwd_w": ("fp8_e4m3", "token"),
        "dgrad_g": ("fp8_e5m2", "token"), "dgrad_w": ("fp8_e4m3", "token"),
        "wgrad_x": ("fp8_e4m3", "token"), "wgrad_g": ("fp8_e5m2", "token")}
_BF16 = {r: None for r in _ROLES}
# the paper's FFN recipe (Sec. 3.2): FP4 forward, per-block activations and
# per-tile weights; FP8 per-block weight gradient; activation gradient kept
# in bfloat16
_FFN_FP4 = {"fwd_x": ("fp4_e2m1", "block"), "fwd_w": ("fp4_e2m1", "tile"),
            "dgrad_g": None, "dgrad_w": None,
            "wgrad_x": ("fp8_e4m3", "block"), "wgrad_g": ("fp8_e5m2", "block")}
RECIPES = {
    "paper_fp4": {"attn": _FP8, "ffn": _FFN_FP4, "head": _BF16},
    "bf16": {"attn": _BF16, "ffn": _BF16, "head": _BF16},
}


def _lowered(recipe: Dict) -> Dict:
    """Every operand one step down: bfloat16 to FP8 per token, FP8 to FP4
    at the same granularity; FP4 has no step below and stays."""
    def low(role, spec):
        if spec is None:
            return ("fp8_e5m2" if role.endswith("_g") else "fp8_e4m3",
                    "token")
        if spec[0].startswith("fp8"):
            return ("fp4_e2m1", spec[1])
        return spec
    return {c: {r: low(r, s) for r, s in roles.items()}
            for c, roles in recipe.items()}


def _round(x, fmt):
    dt, qmax = _ML[fmt]
    return jnp.clip(x, -qmax, qmax).astype(dt).astype(jnp.float32)


def _qdq_rows(x, fmt, gran, block=128):
    """Quantize a 2-D operand whose reduction axis is axis 1."""
    _, qmax = _ML[fmt]
    rows, cols = x.shape
    if gran == "token":
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        s = jnp.maximum(amax, 1e-12) / qmax
        return _round(x / s, fmt) * s
    pr, pc = (-rows) % block, (-cols) % block
    xp = jnp.pad(x, ((0, pr if gran == "tile" else 0), (0, pc)))
    r, c = xp.shape
    if gran == "block":
        xb = xp.reshape(r, c // block, block)
        amax = jnp.max(jnp.abs(xb), axis=2, keepdims=True)
    elif gran == "tile":
        xb = xp.reshape(r // block, block, c // block, block)
        amax = jnp.max(jnp.abs(xb), axis=(1, 3), keepdims=True)
    else:
        raise ValueError(gran)
    s = jnp.maximum(amax, 1e-12) / qmax
    y = (_round(xb / s, fmt) * s).reshape(r, c)
    return y[:rows, :cols]


def _qdq(x, spec, axis):
    if spec is None:
        return x
    fmt, gran = spec
    if axis == 1:
        return _qdq_rows(x, fmt, gran)
    return _qdq_rows(x.T, fmt, gran).T


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qmm(x, w, roles):
    return _qdq(x, roles["fwd_x"], 1) @ _qdq(w, roles["fwd_w"], 0)


def _qmm_fwd(x, w, roles):
    return _qmm(x, w, roles), (x, w)


def _qmm_bwd(roles, res, g):
    x, w = res
    dx = _qdq(g, roles["dgrad_g"], 1) @ _qdq(w.T, roles["dgrad_w"], 0)
    dw = _qdq(x.T, roles["wgrad_x"], 1) @ _qdq(g, roles["wgrad_g"], 0)
    return dx, dw


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


class _Roles(dict):
    """A hashable role table (custom_vjp's static argument)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _linear(x, w, roles):
    lead = x.shape[:-1]
    y = _qmm(x.reshape(-1, x.shape[-1]), w, roles)
    return y.reshape(*lead, w.shape[-1])


def _layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * (1 + p["scale"]) + p["bias"]


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v):
    """Causal softmax attention, (B, S, H, D) x (B, S, KV, D); heads are
    taken a group at a time so the scores stay small."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    q, k, v = (t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
               for t in (q, k, v))
    per = max(1, min(b * h, 2 ** 26 // (s * s)))
    while (b * h) % per:
        per -= 1
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(args):
        qc, kc, vc = args
        sc = jnp.einsum("hqd,hkd->hqk", qc, kc) / np.sqrt(d)
        sc = jnp.where(mask, sc, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, axis=-1), vc)

    n = b * h // per
    out = jax.lax.map(one, (q.reshape(n, per, s, d), k.reshape(n, per, s, d),
                            v.reshape(n, per, s, d)))
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# Rows of the head's block.  Quantization groups of the head's forward and
# activation gradient lie within a row; the control's per-token groups of
# the head's weight gradient (over all tokens) are taken per block of rows.
_HEAD_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class Ref:
    """What the reference needs of a cell: sizes, recipe and optimizer."""

    model: Tuple[Tuple[str, object], ...]
    recipe: str
    lower: bool = False
    half_batch: bool = False
    lr: float = 6e-4
    warmup_steps: int = 1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0

    @property
    def m(self) -> Dict:
        return dict(self.model)

    def roles(self, cls: str) -> _Roles:
        rec = RECIPES[self.recipe]
        if self.lower:
            rec = _lowered(rec)
        return _Roles(rec[cls])


def loss_fn(params, tokens, targets, ref: Ref):
    m = ref.m
    b, s = tokens.shape
    h_, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attn, ffn, head = ref.roles("attn"), ref.roles("ffn"), ref.roles("head")
    x = params["embed"][tokens]
    if m["pos_emb"] == "learned":
        x = x + params["pos_embed"][:s][None]

    @jax.checkpoint
    def layer(x, p):
        h = _layer_norm(x, p["mixer_norm"])
        a = p["mixer"]
        q = _linear(h, a["wq"], attn).reshape(b, s, h_, hd)
        k = _linear(h, a["wk"], attn).reshape(b, s, kv, hd)
        v = _linear(h, a["wv"], attn).reshape(b, s, kv, hd)
        o = _attention(q, k, v).reshape(b, s, h_ * hd)
        x = x + _linear(o, a["wo"], attn)
        h = _layer_norm(x, p["ffn_norm"])
        u = _gelu(_linear(h, p["ffn"]["w_up"], ffn))
        return x + _linear(u, p["ffn"]["w_down"], ffn), None

    x, _ = jax.lax.scan(layer, x, params["stack"]["groups"]["l00"])
    x = _layer_norm(x, params["final_norm"])
    w = params["embed"].T if m["tie_embeddings"] else params["head"]

    # the head and the cross-entropy a block of rows at a time, so that
    # the float32 logits of a block are all that is ever held
    n = b * s
    rows = _HEAD_ROWS if n % _HEAD_ROWS == 0 else n

    @jax.checkpoint
    def block(total, xt):
        xc, tc = xt
        logits = _linear(xc, w, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(
        block, jnp.zeros((), jnp.float32),
        (x.reshape(n // rows, rows, -1), targets.reshape(n // rows, rows)))
    return total / n


def _lr(ref: Ref, step):
    # linear warmup; the cells' first steps all lie inside it
    return ref.lr * (step + 1) / ref.warmup_steps


def grads(params, tokens, targets, ref: Ref):
    """Loss and the gradient clipped by its global norm."""
    if ref.half_batch:      # the planted fault: half the batch left out
        if tokens.shape[0] > 1:
            tokens, targets = tokens[: tokens.shape[0] // 2], \
                targets[: targets.shape[0] // 2]
        else:               # one row: half of its tokens
            tokens, targets = tokens[:, : tokens.shape[1] // 2], \
                targets[:, : targets.shape[1] // 2]
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, targets, ref)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, ref.grad_clip / gnorm), g)
    return loss, g


def adamw(params, g, mu, nu, step, ref: Ref):
    """One decoupled-weight-decay Adam update with bias correction."""
    t = step + 1.0
    lr = _lr(ref, step)

    def upd(decay, p, gi, m, v):
        m = ref.beta1 * m + (1 - ref.beta1) * gi
        v = ref.beta2 * v + (1 - ref.beta2) * gi * gi
        u = (m / (1 - ref.beta1 ** t)) / (
            jnp.sqrt(v / (1 - ref.beta2 ** t)) + ref.eps)
        if decay:
            u = u + ref.weight_decay * p
        return p - lr * u, m, v

    out = jax.tree_util.tree_map_with_path(
        lambda path, *a: upd(_decayed(path), *a), params, g, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def _decayed(path) -> bool:
    """Decoupled weight decay on the matrices (linears, embeddings) only;
    the norms' scales and biases are vectors, per layer."""
    return path[-1].key not in ("scale", "bias")


def run_reference(ref: Ref, params, batches, slice_norms, p0_fn,
                  shardings=None) -> Dict:
    """Training steps from ``params`` on ``batches[i]``; returns the
    losses, the per-slice norms of the first clipped gradient, and of the
    parameters' change over the steps.  ``params`` is consumed.  Adam's
    moments wait on the host while a gradient is computed, so that the
    float32 activations of a whole batch fit beside the weights.
    ``shardings`` (parameters, batch) spreads it over several chips."""
    p_sh, b_sh = shardings or (None, None)
    kw = ({} if p_sh is None else
          {"in_shardings": (p_sh, b_sh, b_sh), "out_shardings": (None, p_sh)})
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(functools.partial(grads, ref=ref), **kw)
        update = jax.jit(functools.partial(adamw, ref=ref),
                         donate_argnums=(0, 1, 2, 3))
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                        out_shardings=p_sh)
        losses, g_norms, moments = [], None, None
        for i, b in enumerate(batches):
            loss, g = grad_fn(params, jnp.asarray(b["tokens"]),
                              jnp.asarray(b["targets"]))
            losses.append(float(loss))
            if i == 0:
                g_norms = np.asarray(slice_norms(g))
                mu, nu = zeros(params), zeros(params)
            else:
                mu, nu = jax.device_put(moments, (p_sh, p_sh)
                                        if p_sh is not None else None)
            params, mu, nu = update(params, g, mu, nu, jnp.float32(i))
            del g
            moments = jax.device_get((mu, nu)) if i + 1 < len(
                batches) else None
            del mu, nu
        d_norms = np.asarray(p0_fn(params))
    return {"losses": losses, "grad_norms": g_norms,
            "update_norms": d_norms}


def readings(cell, seed: int, batches, *, lower: bool = False,
             half_batch: bool = False) -> Dict:
    """The reference's readings for ``cell`` from ``seed``'s weights on the
    checked steps' ``batches``; ``lower`` gives the control, ``half_batch``
    a planted fault (the mean over half the rows, or over the first half
    of the tokens of a batch of one row)."""
    import weights
    w = cell.workload
    ref = Ref(model=tuple(sorted(cell.model.items())), recipe=w["recipe"],
              lower=lower, half_batch=half_batch, lr=w["learning_rate"],
              warmup_steps=max(int(w["total_steps"] * w["warmup_frac"]), 1))
    lay = weights.layout(cell.model)
    key = weights.seed_key(seed)
    shardings = _shardings(lay, cell.chips)
    p0 = jax.jit(lambda k: weights.init_params(lay, k),
                 out_shardings=shardings[0] if shardings else None)(key)
    norms = jax.jit(lambda t: weights.slice_norms(t, lay))
    delta = jax.jit(lambda t, k: weights.slice_norms(jax.tree.map(
        jnp.subtract, t, weights.init_params(lay, k)), lay))
    return run_reference(ref, p0, batches, norms, lambda p: delta(p, key),
                         shardings=shardings)


def _shardings(lay, chips: int):
    """Over several chips: each parameter split along its largest axis
    that the chips divide, the batch split by rows."""
    if chips <= 1:
        return None
    import weights
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:chips]), ("d",))

    def spec(shape):
        axes = [i for i in np.argsort(shape)[::-1] if shape[i] % chips == 0]
        if not axes:
            return NamedSharding(mesh, P())
        parts = [None] * len(shape)
        parts[axes[0]] = "d"
        return NamedSharding(mesh, P(*parts))
    p_sh = weights._nest({path: spec(shape)
                          for path, (shape, _) in lay.items()})
    return p_sh, NamedSharding(mesh, P("d"))
