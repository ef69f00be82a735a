"""An architecture whose layers are of two kinds, for the test that a new
architecture joins the benchmark with new files only
(``test_bench_rehearsal.py``): one leading dense SwiGLU layer, then
sparse-expert layers (a float router, the top ``k`` of ``E`` SwiGLU
experts, their gates renormalised over the ``k``), GQA attention in
every layer. The test copies it into ``bench/archs/``. Sizes use
DeepSeek-MoE's configuration keys beside the dense ones.

Layer 0 has other leaves than the rest (a (d, d_ff) feed-forward against
a router and stacked (E, d, d_expert) experts), so the reference makes
and applies each kind apart (``layer_kind``). It counts no work: its
readers report nothing.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from harness import common as C
from harness import weights as W
from harness.reference import HIGHEST, rmsnorm, rtn

G = C.load_arch({"arch": "dense_gqa"})

small = {**G.small, "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "n_routed_experts": 4, "num_experts_per_tok": 2,
         "moe_intermediate_size": 64}
FFN = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Dims(G.Dims):
    n_dense: int
    n_experts: int
    top_k: int
    d_expert: int


def dims(c: dict) -> Dims:
    return Dims(**dataclasses.asdict(G.Dims.from_config(c)),
                n_dense=c["first_k_dense_replace"],
                n_experts=c["n_routed_experts"],
                top_k=c["num_experts_per_tok"],
                d_expert=c["moe_intermediate_size"])


def arch_config(config: dict, d: Dims):
    from repro.configs.base import MoEArch

    return dataclasses.replace(
        G.arch_config(config, d), family="moe",
        moe=MoEArch(n_experts=d.n_experts, top_k=d.top_k,
                    d_ff_expert=d.d_expert, first_k_dense=d.n_dense,
                    first_dense_ff=d.d_ff))


def layer_kind(d: Dims, i: int) -> str:
    return "dense" if i < d.n_dense else "moe"


def layer(d: Dims, lo, hi, i, kind: str) -> dict:
    """Layer ``i``'s float32 leaves; each expert's from a key of its own."""
    if kind == "dense":
        return G.layer(d, lo, hi, i)
    key = W.layer_key(lo, hi, i)
    shapes = {n: d.leaf_shape(n) for n in G.LAYER_LEAVES if n not in FFN}
    out = W.leaves(key, {**shapes, "router": (d.d_model, d.n_experts)})
    f = d.d_expert
    experts = [W.leaves(jax.random.fold_in(key, 1000 + e),
                        {"w_gate": (d.d_model, f), "w_up": (d.d_model, f),
                         "w_down": (f, d.d_model)})
               for e in range(d.n_experts)]
    return {**out, **{n: jnp.stack([x[n] for x in experts]) for n in FFN}}


def program_tree(d: Dims, lo, hi) -> dict:
    """The float32 tree in the layout of ``repro.models.LM``'s ``moe``
    family: a ``dense0`` stack, then a ``moe`` stack."""
    o = W.outer(d, lo, hi)

    def stack(kind, idx):
        layers = [layer(d, lo, hi, i, kind) for i in idx]

        def s(n):
            return {"w": jnp.stack([lay[n] for lay in layers])}

        sub = {"norm1": {"g": s("norm1")["w"]}, "norm2": {"g": s("norm2")["w"]},
               "attn": {n: s(n) for n in ("wq", "wk", "wv", "wo")}}
        if kind == "dense":
            sub["mlp"] = {n: s(n) for n in FFN}
        else:
            sub["moe"] = {n: s(n) for n in ("router", *FFN)}
        return {"sub0": sub}

    tree = {"embed": {"table": o["embed"]}, "final_norm": {"g": o["final_norm"]},
            "moe": stack("moe", range(d.n_dense, d.n_layers))}
    if d.n_dense:
        tree["dense0"] = stack("dense", range(d.n_dense))
    if not d.tie:
        tree["head"] = {"w": o["head"]}
    return tree


def served_maker(d: Dims, pack):
    return jax.jit(lambda lo, hi: pack(program_tree(d, lo, hi)))


def reference_weights(d: Dims, w_bits: int):
    """The dense module's outer weights; each layer's linears under
    round-to-nearest, expert by expert, and the router as it is (the
    program keeps it in float)."""
    outer, _ = G.reference_weights(d, w_bits)

    def quant(n, a):
        if n.startswith("norm") or n == "router":
            return a
        return jax.vmap(lambda m: rtn(m, w_bits))(a) if a.ndim == 3 else rtn(a, w_bits)

    def layer_w(lo, hi, i, kind):
        return {n: quant(n, a) for n, a in layer(d, lo, hi, i, kind).items()}

    return outer, layer_w


reference_layers = G.reference_layers


def block(d: Dims, i, w: dict, x, operands=None, kv8: bool = True,
          kind: str = "dense"):
    x = G.attend(d, w, x, operands, kv8)
    h = rmsnorm(x, w["norm2"], d.norm_eps)
    if kind == "dense":
        return x + G.swiglu(w, h, operands)
    mm, _ = G.matmuls(operands)
    probs = jax.nn.softmax(mm(h, w["router"]), axis=-1)          # (S, E)
    gates, idx = jax.lax.top_k(probs, d.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    comb = jnp.zeros_like(probs).at[rows, idx].set(gates)
    ys = jax.vmap(lambda *e: G.swiglu(dict(zip(FFN, e)), h, operands))(
        *(w[n] for n in FFN))                                     # (E, S, d)
    return x + jnp.einsum("se,esd->sd", comb, ys, precision=HIGHEST)


def _unknown(*_args):
    return None


decode_qmm_s = chunk_qmm_s = kv_min_time = _unknown
model_flops_decode = chunk_flops = _unknown
