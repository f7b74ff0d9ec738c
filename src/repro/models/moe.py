"""Mixture-of-experts FFN: dropless top-k routing over a grouped matmul.

The layer routes every token over all ``E`` experts the router scores,
sorts the (token, expert) pairs by expert and runs the experts it holds as
three grouped matmuls (gate, up, down) over the sorted rows. No token is
dropped, whatever the routing's skew. The layer holds a contiguous range
``[first, first + n_held)`` of the ``E`` experts, read from its weights'
leading axis: pairs routed elsewhere contribute nothing here, as on a chip
of an expert-parallel deployment that holds this share. Shared experts
(DeepSeek-V2) are an always-on dense MLP added alongside the routed path.

Inside a scan over stacked layers the expert matrices are not sliced per
layer: a slice handed to a Pallas call is copied out first, on every call
(a Mixtral layer's three are 2.8 GB), so ``moe_ffn`` takes the whole stack
and the layer's index (``layer``), and the grouped-matmul kernel reads the
layer's experts in place.

Named scopes ``moe.route``, ``moe.experts`` and ``moe.combine`` label the
layer's parts in a profile's op metadata.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init

__all__ = ["init_moe", "moe_ffn", "init_mlp", "mlp", "EXPERT_MATS"]


# ---------------------------------------------------------------------------
# dense (gated SwiGLU) MLP — also used for shared experts and dense layers
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": dense_init(k1, (d, ff), 0, dtype),
        "wu": dense_init(k2, (d, ff), 0, dtype),
        "wd": dense_init(k3, (ff, d), 0, dtype),
    }


def mlp(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
    return h @ p["wd"]


# ---------------------------------------------------------------------------
# routed experts
# ---------------------------------------------------------------------------


def init_moe(key, d: int, eff: int, n_experts: int, n_shared: int, dtype) -> dict:
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, n_experts), 0, jnp.float32),
        "wg": dense_init(ks[1], (n_experts, d, eff), 1, dtype),
        "wu": dense_init(ks[2], (n_experts, d, eff), 1, dtype),
        "wd": dense_init(ks[3], (n_experts, eff, d), 1, dtype),
    }
    if n_shared:
        p["shared"] = init_mlp(ks[4], d, n_shared * eff, dtype)
    return p


# the routed experts' matrices, leading axis the experts held
EXPERT_MATS = ("wg", "wu", "wd")


def _grouped(service, lhs, rhs, sizes, layer):
    """One grouped matmul over the experts of ``rhs`` ``(E, k, n)``, or of
    its layer ``layer`` where ``rhs`` is a stack ``(L, E, k, n)``: the
    dispatched kernel with a service, else XLA's ``ragged_dot``. The kernel
    takes a stack whole, as ``L * E`` groups of which only this layer's
    hold rows, so that no layer's matrices are copied out of it."""
    if service is None:
        if layer is not None:
            rhs = jax.lax.dynamic_index_in_dim(rhs, layer, keepdims=False)
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    if layer is not None:
        L, E = rhs.shape[:2]
        rhs = rhs.reshape(L * E, *rhs.shape[2:])
        sizes = jax.lax.dynamic_update_slice(jnp.zeros(L * E, sizes.dtype), sizes,
                                             (layer * E,))
    return service.dispatch("grouped_matmul", lhs, rhs, sizes)(lhs, rhs, sizes)


def moe_ffn(
    p: dict,
    x: jnp.ndarray,             # (B, S, d)
    *,
    top_k: int,
    first: int = 0,
    service=None,
    layer=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss). Router logits in float32 over all ``E``
    experts, a softmax, the top ``top_k`` renormalised over themselves.
    The experts held are ``p["wg"]``'s leading axis, ``first`` onwards;
    ``service`` (a :class:`repro.dispatch.DispatchService`) routes the
    grouped matmuls through the tuned ``grouped_matmul`` kernel. With
    ``layer`` (a traced index), the expert matrices are the stacks of every
    layer and this is layer ``layer``."""
    B, S, d = x.shape
    E = p["router"].shape[1]
    n_held = p["wg"].shape[-3]
    T = B * S
    xt = x.reshape(T, d)

    with jax.named_scope("moe.route"):
        probs = jax.nn.softmax(xt.astype(jnp.float32) @ p["router"], axis=-1)
        gate, idx = jax.lax.top_k(probs, top_k)                      # (T, k)
        gate = gate / gate.sum(-1, keepdims=True)
        # experts counted from the first held one: held pairs sort first, in
        # expert order, and the rows past them belong to other chips' experts
        local = (idx - first) % E
        order = jnp.argsort(local.reshape(-1), stable=True)         # (T*k,)
        sizes = jnp.sum(jax.nn.one_hot(local, n_held, dtype=jnp.int32),
                        axis=(0, 1))                                 # (n_held,)
        xs = xt[order // top_k]                                      # (T*k, d)

    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(_grouped(service, xs, p["wg"], sizes, layer)) \
            * _grouped(service, xs, p["wu"], sizes, layer)
        ys = _grouped(service, h, p["wd"], sizes, layer)             # (T*k, d)

    with jax.named_scope("moe.combine"):
        # back to (token, choice) order, weighted by the gates; a pair that
        # went to an expert not held here adds nothing
        yk = ys[jnp.argsort(order)].reshape(T, top_k, d).astype(jnp.float32)
        w = jnp.where(local < n_held, gate, 0.0)
        y = jnp.einsum("tk,tkd->td", w, yk).astype(x.dtype).reshape(B, S, d)

    # load-balancing aux loss (Switch/GShard): routed fraction x mean prob
    frac = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(jnp.mean(probs, axis=0) * frac)

    if "shared" in p:
        y = y + mlp(p["shared"], x)
    return y, aux
