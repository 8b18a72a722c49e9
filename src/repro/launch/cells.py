"""Cell builder: (architecture x input-shape x mesh) -> lowerable problem.

For every cell of the dry-run matrix this produces:

  * ``fn``            — the step function (train_step / serve_step / ...)
  * ``args``          — ShapeDtypeStruct stand-ins with NamedShardings
                        attached (weak-type-correct, shardable, ZERO device
                        allocation — 400B-param trees stay abstract)
  * ``out_shardings`` — explicit output placement (params/opt keep their
                        input sharding; metrics replicate)
  * ``static``        — bookkeeping: model/active param counts, MODEL_FLOPS
                        (6ND / 2ND conventions), bytes-level notes

``kind`` semantics: ``decode_*``/``long_*`` lower **serve_step** (one new
token against a seq_len KV cache), NOT train_step; encoder/serve recsys
cells lower forward-only steps (see the assignment brief).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.common import ArchSpec, Cell
from repro.core import distributed as ann_dist
from repro.core.types import FakeWordsIndex
from repro.models import gnn as gnn_mod
from repro.models import recsys as rec_mod
from repro.models import transformer as tfm
from repro.sharding import rules
from repro.train import optimizer as opt_mod

Pytree = Any


@dataclasses.dataclass
class CellBuild:
    arch_id: str
    cell: Cell
    fn: Callable
    args: Tuple
    out_shardings: Any
    static: Dict[str, Any]
    donate: Tuple[int, ...] = ()  # donated arg positions (state buffers
    #                               update in place: train state, KV cache)
    mesh: Optional[Mesh] = None

    def jitted(self):
        if hasattr(self.fn, "lower"):  # pre-jitted (ANN shard_map path)
            return self.fn
        return jax.jit(
            self.fn, out_shardings=self.out_shardings, donate_argnums=self.donate
        )

    def lower(self):
        # Mesh context: the step fns constrain activations with bare
        # PartitionSpecs (models don't hold mesh objects).
        with jax.set_mesh(self.mesh):
            return self.jitted().lower(*self.args)


def _sds(shape, dtype, mesh, spec) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _replicated_like(struct_tree, mesh):
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), struct_tree)


def _make_opt(arch: ArchSpec) -> opt_mod.Optimizer:
    return opt_mod.adamw() if arch.optimizer == "adamw" else opt_mod.adafactor()


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _param_structs(shapes, specs, dtype, mesh):
    return jax.tree_util.tree_map(
        lambda s, p: _sds(s, dtype, mesh, p), shapes, specs,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def _opt_structs(opt, param_structs, opt_specs, mesh):
    shapes = jax.eval_shape(opt.init, param_structs)
    return jax.tree_util.tree_map(
        lambda st, sp: _sds(st.shape, st.dtype, mesh, sp), shapes,
        _to_tree_of_specs(opt_specs),
    )


def _to_tree_of_specs(tree):
    return tree


# --------------------------------------------------------------------------
# MODEL_FLOPS conventions (per §Roofline)
# --------------------------------------------------------------------------


def lm_model_flops(cfg: tfm.TransformerConfig, cell: Cell) -> float:
    total, active = cfg.param_count()
    b, s = cell.batch, cell.seq
    hqd = cfg.n_heads * cfg.dh
    if cell.kind == "train":
        tokens = b * s
        attn = 3 * 2 * b * s * s * hqd * cfg.n_layers  # fwd+bwd, causal-halved
        return 6.0 * active * tokens + attn
    if cell.kind == "prefill":
        tokens = b * s
        attn = 2 * b * s * s * hqd * cfg.n_layers * 0.5 * 2  # qk+av causal
        return 2.0 * active * tokens + attn
    # decode: one token per sequence against a seq_len cache
    attn = 4.0 * b * cell.seq * hqd * cfg.n_layers
    return 2.0 * active * b + attn


def gnn_model_flops(cfg: gnn_mod.SageConfig, cell: Cell) -> float:
    d0, dh, c = cfg.d_in, cfg.d_hidden, cfg.n_classes
    if cell.kind in ("full_graph",):
        n, e = cell.get("n_nodes"), cell.get("n_edges")
        mm = 2 * n * (d0 * dh * 2 + dh * dh * 2 + dh * c)
        agg = e * (d0 + dh)
        return 3.0 * (mm + agg)  # fwd + bwd ~ 3x fwd
    if cell.kind == "minibatch":
        b = cell.batch
        f1, f2 = cell.get("fanouts")
        rows0 = b * (1 + f1 + f1 * f2)  # layer-0 combines
        rows1 = b * (1 + f1)
        mm = 2 * rows0 * d0 * dh * 2 + 2 * rows1 * dh * dh * 2 + 2 * b * dh * c
        return 3.0 * mm
    # molecule: batched small graphs
    g, n, e = cell.batch, cell.get("n_nodes"), cell.get("n_edges")
    mm = 2 * g * n * (d0 * dh * 2 + dh * dh * 2) + 2 * g * dh * c
    agg = g * e * (d0 + dh)
    return 3.0 * (mm + agg)


def recsys_model_flops(cfg: rec_mod.RecsysConfig, cell: Cell) -> float:
    f, d = cfg.n_fields, cfg.dim

    def mlp_flops(widths, d_in):
        fl, prev = 0, d_in
        for w in widths:
            fl += 2 * prev * w
            prev = w
        return fl

    per_ex = 2 * f * d  # embedding reduce + fm trick
    if cfg.model == "deepfm":
        per_ex += mlp_flops(cfg.mlp + (1,), f * d)
    elif cfg.model == "dlrm":
        per_ex = mlp_flops(cfg.bot_mlp, cfg.n_dense)
        n_vec = f + 1
        per_ex += 2 * n_vec * n_vec * d  # gram
        per_ex += mlp_flops(cfg.top_mlp, n_vec * (n_vec - 1) // 2 + cfg.bot_mlp[-1])
    elif cfg.model == "xdeepfm":
        per_ex += mlp_flops(cfg.mlp + (1,), f * d)
        prev = f
        for h in cfg.cin_layers:
            per_ex += 2 * prev * f * d * h
            prev = h
    if cell.kind == "train":
        return 3.0 * cell.batch * per_ex
    if cell.kind == "retrieval":
        n_cand = cell.get("n_candidates")
        return cell.batch * per_ex + 2.0 * cell.batch * n_cand * d
    return float(cell.batch * per_ex)


# --------------------------------------------------------------------------
# LM cells
# --------------------------------------------------------------------------


def _build_lm(arch: ArchSpec, cell: Cell, mesh: Mesh, multi_pod: bool,
              cfg: Optional[tfm.TransformerConfig] = None) -> CellBuild:
    cfg = cfg or arch.make_model(cell)
    # Pin activation shardings (residual/logits/KV) to the production mesh;
    # long-context decode spreads the KV length over every axis.
    long = bool(cell.get("long"))
    cfg = dataclasses.replace(
        cfg,
        batch_axes=() if cell.batch == 1 else rules.batch_axes(multi_pod),
        tp_axis=rules.TP,
        kv_axes=(rules.all_axes(multi_pod) if long else rules.TP)
        if cell.kind in ("prefill", "decode") else None,
        # Flat-GQA whenever kv heads don't fill the TP axis: avoids GSPMD
        # splitting the GQA group dim into partial-reduce groups (§Perf A2).
        attn_flat_heads=cfg.n_kv_heads < 16 and cell.kind in ("train", "prefill"),
    )
    opt = _make_opt(arch)
    shapes = tfm.param_shapes(cfg)
    pspecs = rules.lm_param_specs(shapes)
    params = _param_structs(shapes, pspecs, cfg.param_dtype, mesh)
    batch_sp = rules.lm_batch_spec(multi_pod)
    total, active = cfg.param_count()
    static = {
        "params_total": total, "params_active": active,
        "model_flops": lm_model_flops(cfg, cell),
    }

    if cell.kind == "train":
        ospecs = rules.opt_state_specs(arch.optimizer, pspecs, shapes)
        opt_state = _opt_structs(opt, params, ospecs, mesh)
        tokens = _sds((cell.batch, cell.seq), jnp.int32, mesh, batch_sp)
        labels = _sds((cell.batch, cell.seq), jnp.int32, mesh, batch_sp)
        # Microbatch accumulation: per-device remat checkpoints are
        # L x (B_local/m) x S x d x 2 bytes; pick m so they stay <= ~4 GB
        # (global batch and numerics unchanged; m is a §Perf lever).
        dp_shards = 1
        for ax in rules.batch_axes(multi_pod):
            dp_shards *= mesh.shape[ax]
        ckpt_bytes = (
            cfg.n_layers * (cell.batch / dp_shards) * cell.seq * cfg.d_model * 2
        )
        n_micro = int(cell.get("n_microbatches", 0))
        if not n_micro:
            n_micro = 1
            while ckpt_bytes / n_micro > 4e9 and n_micro < cell.batch // dp_shards:
                n_micro *= 2
        static["n_microbatches"] = n_micro

        # ZeRO-2 + mixed precision (§Perf iterations 2-3): the f32 master +
        # optimizer states stay fully sharded (model x data); ONE bf16
        # compute copy per step is constrained data-REPLICATED, so weights
        # all-gather once (bf16) instead of per-layer/per-pass, and GSPMD
        # stops AR-ing (b,s,d) activations over 'data' (measured: the
        # dominant collective).  Grads are constrained back to the master
        # sharding => reduce-scatter over 'data'.
        # ZeRO-2 only if the data-replicated bf16 copy fits comfortably:
        # per-device copy = 2 bytes x total params / model-axis shards (<=3GB).
        # llama4-maverick (400B): 50 GB/dev => keep the compute copy FSDP-
        # sharded there (weights re-gather per layer, the standard FSDP
        # cost) — recorded in EXPERIMENTS.md §Perf A3.
        zero2_ok = 2.0 * total / mesh.shape[rules.TP] <= 3e9
        zero2_specs = jax.tree_util.tree_map(
            lambda sp: (rules.drop_axis(sp, rules.FSDP) if zero2_ok else sp),
            pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        static["zero2"] = bool(zero2_ok)

        def train_step(params, opt_state, tokens, labels):
            def compute_cast(p, sp):
                pc = p.astype(cfg.dtype) if p.ndim >= 2 else p
                return jax.lax.with_sharding_constraint(pc, sp)

            def loss_cast(params_c, tokens, labels):
                return tfm.loss_fn(params_c, tokens, labels, cfg)

            params_c = jax.tree_util.tree_map(compute_cast, params, zero2_specs)
            if n_micro == 1:
                loss, grads = jax.value_and_grad(loss_cast)(
                    params_c, tokens, labels
                )
            else:
                tok_m = tokens.reshape(n_micro, cell.batch // n_micro, cell.seq)
                lab_m = labels.reshape(n_micro, cell.batch // n_micro, cell.seq)

                def acc(carry, tl):
                    loss_acc, grad_acc = carry
                    t, l = tl
                    t = jax.lax.with_sharding_constraint(t, batch_sp)
                    l = jax.lax.with_sharding_constraint(l, batch_sp)
                    loss_i, grads_i = jax.value_and_grad(loss_cast)(params_c, t, l)
                    grads_i = jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.float32), grads_i)
                    return (
                        loss_acc + loss_i,
                        jax.tree_util.tree_map(jnp.add, grad_acc, grads_i),
                    ), None

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                (loss, grads), _ = jax.lax.scan(
                    acc, (jnp.zeros((), jnp.float32), zeros), (tok_m, lab_m)
                )
                loss = loss / n_micro
                grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
            # reduce-scatter grads back to the master's FSDP sharding
            grads = jax.tree_util.tree_map(
                lambda g, sp: jax.lax.with_sharding_constraint(
                    g.astype(jnp.float32), sp),
                grads, pspecs,
            )
            new_p, new_s, info = opt.update(grads, opt_state, params)
            return new_p, new_s, {"loss": loss, **info}

        metrics_struct = jax.eval_shape(
            train_step, params, opt_state, tokens, labels
        )[2]
        out_sh = (
            _named(mesh, pspecs),
            _named(mesh, ospecs),
            _replicated_like(metrics_struct, mesh),
        )
        return CellBuild(arch.id, cell, train_step, (params, opt_state, tokens, labels), out_sh, static, donate=(0, 1))

    if cell.kind == "prefill":
        tokens = _sds((cell.batch, cell.seq), jnp.int32, mesh, batch_sp)

        def serve_step(params, tokens):
            return tfm.prefill(params, tokens, cfg)

        cache_spec = rules.lm_cache_spec(multi_pod)
        out_sh = (
            {
                "k": NamedSharding(mesh, cache_spec),
                "v": NamedSharding(mesh, cache_spec),
                "length": NamedSharding(mesh, P()),
            },
            NamedSharding(mesh, rules.lm_logit_spec(multi_pod)),
        )
        return CellBuild(arch.id, cell, serve_step, (params, tokens), out_sh, static)

    # decode (decode_32k / long_500k): one new token against a seq_len cache
    cache_spec = rules.lm_cache_spec(multi_pod, long_context=long)
    cache = {
        "k": _sds((cfg.n_layers, cell.batch, cell.seq, cfg.n_kv_heads, cfg.dh),
                  cfg.dtype, mesh, cache_spec),
        "v": _sds((cfg.n_layers, cell.batch, cell.seq, cfg.n_kv_heads, cfg.dh),
                  cfg.dtype, mesh, cache_spec),
        "length": jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
    }
    token = _sds((cell.batch,), jnp.int32, mesh,
                 P(rules.batch_axes(multi_pod)) if cell.batch > 1 else P())

    def serve_step(params, cache, token):
        return tfm.decode_step(params, cache, token, cfg)

    out_sh = (
        {
            "k": NamedSharding(mesh, cache_spec),
            "v": NamedSharding(mesh, cache_spec),
            "length": NamedSharding(mesh, P()),
        },
        NamedSharding(
            mesh,
            P(rules.batch_axes(multi_pod), rules.TP) if cell.batch > 1 else P(None, rules.TP),
        ),
    )
    return CellBuild(arch.id, cell, serve_step, (params, cache, token), out_sh, static, donate=(1,))


# --------------------------------------------------------------------------
# GNN cells
# --------------------------------------------------------------------------


def _build_gnn(arch: ArchSpec, cell: Cell, mesh: Mesh, multi_pod: bool) -> CellBuild:
    cfg = arch.make_model(cell)
    opt = _make_opt(arch)
    shapes = gnn_mod.param_shapes(cfg)
    pspecs = rules.gnn_param_specs(shapes)
    params = _param_structs(shapes, pspecs, jnp.float32, mesh)
    ospecs = rules.opt_state_specs(arch.optimizer, pspecs, shapes)
    opt_state = _opt_structs(opt, params, ospecs, mesh)
    static = {
        "params_total": sum(
            int(jnp.prod(jnp.asarray(s))) for s in jax.tree_util.tree_leaves(
                shapes, is_leaf=lambda x: isinstance(x, tuple))
        ),
        "model_flops": gnn_model_flops(cfg, cell),
    }
    static["params_active"] = static["params_total"]

    def finish(loss_fn_args, fn_args):
        def train_step(params, opt_state, *args):
            loss, grads = jax.value_and_grad(loss_fn_args)(params, *args)
            new_p, new_s, info = opt.update(grads, opt_state, params)
            return new_p, new_s, {"loss": loss, **info}

        metrics_struct = jax.eval_shape(train_step, params, opt_state, *fn_args)[2]
        out_sh = (
            _named(mesh, pspecs),
            _named(mesh, ospecs),
            _replicated_like(metrics_struct, mesh),
        )
        return CellBuild(
            arch.id, cell, train_step, (params, opt_state) + fn_args, out_sh,
            static, donate=(0, 1),
        )

    if cell.kind == "full_graph":
        n, e = cell.get("n_nodes"), cell.get("n_edges")
        # Pad the edge list to a mesh-divisible length; pad edges carry
        # dst = n_nodes, which segment_sum (num_segments = n) drops — they
        # contribute nothing to messages or degrees.
        e_pad = -(-e // 512) * 512
        edge_sp = rules.gnn_edge_spec(multi_pod)
        feats = _sds((n, cfg.d_in), jnp.float32, mesh, P())
        src = _sds((e_pad,), jnp.int32, mesh, edge_sp)
        dst = _sds((e_pad,), jnp.int32, mesh, edge_sp)
        labels = _sds((n,), jnp.int32, mesh, P())
        mask = _sds((n,), jnp.float32, mesh, P())

        def loss(params, feats, src, dst, labels, mask):
            return gnn_mod.loss_full(params, feats, src, dst, labels, mask, cfg)

        return finish(loss, (feats, src, dst, labels, mask))

    if cell.kind == "minibatch":
        n, b = cell.get("n_nodes"), cell.batch
        f1, f2 = cfg.fanouts
        bsp = rules.gnn_minibatch_spec(multi_pod, 1)
        feats = _sds((n, cfg.d_in), jnp.float32, mesh, P())
        batch_nodes = _sds((b,), jnp.int32, mesh, bsp)
        nbr1 = _sds((b, f1), jnp.int32, mesh, rules.gnn_minibatch_spec(multi_pod, 2))
        nbr2 = _sds((b, f1, f2), jnp.int32, mesh, rules.gnn_minibatch_spec(multi_pod, 3))
        labels = _sds((b,), jnp.int32, mesh, bsp)

        def loss(params, feats, batch_nodes, nbr1, nbr2, labels):
            return gnn_mod.loss_sampled(params, feats, batch_nodes, nbr1, nbr2, labels, cfg)

        return finish(loss, (feats, batch_nodes, nbr1, nbr2, labels))

    # molecule: batched small graphs
    g, n, e = cell.batch, cell.get("n_nodes"), cell.get("n_edges")
    bsp = rules.batch_axes(multi_pod)
    feats = _sds((g, n, cfg.d_in), jnp.float32, mesh, P(bsp, None, None))
    src = _sds((g, e), jnp.int32, mesh, P(bsp, None))
    dst = _sds((g, e), jnp.int32, mesh, P(bsp, None))
    labels = _sds((g,), jnp.int32, mesh, P(bsp))

    def loss(params, feats, src, dst, labels):
        return gnn_mod.loss_batched(params, feats, src, dst, labels, cfg)

    return finish(loss, (feats, src, dst, labels))


# --------------------------------------------------------------------------
# RecSys cells
# --------------------------------------------------------------------------


def _build_recsys(arch: ArchSpec, cell: Cell, mesh: Mesh, multi_pod: bool) -> CellBuild:
    cfg = arch.make_model(cell)
    opt = _make_opt(arch)
    shapes = rec_mod.param_shapes(cfg)
    pspecs = rules.recsys_param_specs(shapes)
    params = _param_structs(shapes, pspecs, cfg.param_dtype, mesh)
    static = {
        "params_total": cfg.param_count(),
        "params_active": cfg.param_count(),
        "model_flops": recsys_model_flops(cfg, cell),
    }
    b = cell.batch
    bsp2 = rules.recsys_batch_spec(multi_pod, 2)
    bsp3 = rules.recsys_batch_spec(multi_pod, 3)
    bsp1 = rules.recsys_batch_spec(multi_pod, 1)

    def batch_structs(batch_size, spec_batched=True):
        mk = lambda shape, dt, sp: _sds(shape, dt, mesh, sp)
        rep = P(*(None,) * 3)
        out = {
            "sparse": mk((batch_size, cfg.n_fields, cfg.nnz), jnp.int32,
                         bsp3 if spec_batched else rep),
        }
        if cfg.n_dense:
            out["dense"] = mk((batch_size, cfg.n_dense), jnp.float32,
                              bsp2 if spec_batched else P(None, None))
        return out

    if cell.kind == "train":
        ospecs = rules.opt_state_specs(arch.optimizer, pspecs, shapes)
        opt_state = _opt_structs(opt, params, ospecs, mesh)
        batch = batch_structs(b)
        label = _sds((b,), jnp.float32, mesh, bsp1)

        def train_step(params, opt_state, batch, label):
            def loss_of(p, batch):
                return rec_mod.bce_loss(p, cfg, batch["sparse"], label, batch.get("dense"))

            loss, grads = jax.value_and_grad(loss_of)(params, batch)
            new_p, new_s, info = opt.update(grads, opt_state, params)
            return new_p, new_s, {"loss": loss, **info}

        metrics_struct = jax.eval_shape(train_step, params, opt_state, batch, label)[2]
        out_sh = (
            _named(mesh, pspecs),
            _named(mesh, ospecs),
            _replicated_like(metrics_struct, mesh),
        )
        return CellBuild(arch.id, cell, train_step, (params, opt_state, batch, label), out_sh, static, donate=(0, 1))

    if cell.kind == "serve":
        batch = batch_structs(b)

        def serve_step(params, batch):
            logit = rec_mod.forward(params, cfg, batch["sparse"], batch.get("dense"))
            return jax.nn.sigmoid(logit)

        out_sh = NamedSharding(mesh, P(rules.batch_axes(multi_pod)))
        return CellBuild(arch.id, cell, serve_step, (params, batch), out_sh, static)

    # retrieval_cand: one query context vs n_candidates item vectors.
    # The candidate buffer is padded up to a mesh-divisible row count
    # (pad rows are zeros) and pad scores are masked to -inf before top-k.
    n_cand = cell.get("n_candidates")
    n_pad = -(-n_cand // 512) * 512
    batch = batch_structs(b, spec_batched=False)  # B=1: replicate
    cand = _sds((n_pad, cfg.dim), jnp.float32, mesh, rules.recsys_cand_spec(multi_pod))

    def retrieval_step(params, batch, cand):
        u = rec_mod.user_tower(params, cfg, batch["sparse"], batch.get("dense"))
        scores = rec_mod.retrieval_scores(u, cand)  # (B, n_pad)
        valid = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) < n_cand
        scores = jnp.where(valid, scores, -jnp.inf)
        top_s, top_i = jax.lax.top_k(scores, 100)
        return top_s, top_i  # force tuple (lax.top_k yields a list pytree)

    out_sh = (NamedSharding(mesh, P()), NamedSharding(mesh, P()))
    return CellBuild(arch.id, cell, retrieval_step, (params, batch, cand), out_sh, static)


# --------------------------------------------------------------------------
# ANN (paper-own) cells
# --------------------------------------------------------------------------


def _build_ann(arch: ArchSpec, cell: Cell, mesh: Mesh, multi_pod: bool) -> CellBuild:
    config = arch.make_model(cell)
    n, dim = cell.get("n_docs"), cell.get("dim")
    m2 = 2 * dim
    b = cell.batch
    axes = rules.all_axes(multi_pod)
    doc_sp = P(axes, None)
    rerank_dtype = jnp.bfloat16 if cell.get("rerank_dtype") == "bfloat16" else jnp.float32

    tf_cols = (m2 // 2) if getattr(config, "signed_store", False) else m2
    index = FakeWordsIndex(
        tf=_sds((n, tf_cols), jnp.int8, mesh, doc_sp),
        idf=_sds((m2,), jnp.float32, mesh, P()),
        norm=_sds((n,), jnp.float32, mesh, P(axes)),
        df=_sds((m2,), jnp.int32, mesh, P()),
        scored=(_sds((n, m2), jnp.bfloat16, mesh, doc_sp)
                if config.scoring == "classic" else None),
        vectors=_sds((n, dim), rerank_dtype, mesh, doc_sp),
    )
    q_tf = _sds((b, m2), jnp.int32, mesh, P())
    queries = _sds((b, dim), rerank_dtype, mesh, P())

    fn = ann_dist.make_sharded_search(
        mesh, config, axes, k=cell.get("k", 10), depth=cell.get("depth", 100),
        rerank=True, tile_unroll=bool(cell.get("tile_unroll", False)),
    )
    static = {
        "params_total": 0, "params_active": 0,
        # §Roofline convention: 2 * N_q * N_d * dims (the ideal dot-scoring
        # work; the sign-split GEMM does 2x this, the signed store 1x).
        "model_flops": 2.0 * b * n * dim,
    }
    return CellBuild(arch.id, cell, fn, (index, q_tf, queries), None, static)


# --------------------------------------------------------------------------
# Entry
# --------------------------------------------------------------------------

_BUILDERS = {
    "lm": _build_lm,
    "gnn": _build_gnn,
    "recsys": _build_recsys,
    "ann": _build_ann,
}


def build_cell(arch: ArchSpec, cell: Cell, mesh: Mesh, multi_pod: bool,
               **kw) -> CellBuild:
    with jax.set_mesh(mesh):  # builders eval_shape through constrained fns
        built = _BUILDERS[arch.family](arch, cell, mesh, multi_pod, **kw)
    built.mesh = mesh
    return built


def input_specs(arch: ArchSpec, cell_name: str, mesh: Mesh, multi_pod: bool = False):
    """ShapeDtypeStruct stand-ins for every input of the cell's step
    (params, optimizer state, batch/cache), shardings attached."""
    cell = arch.cell(cell_name)
    return build_cell(arch, cell, mesh, multi_pod).args
