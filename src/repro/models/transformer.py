"""Composable decoder LM over a per-layer block pattern.

One ``Model`` covers all ten assigned architectures:

- the config's ``block_pattern`` is split into (prelude, scanned
  super-blocks, postlude) — e.g. DeepSeek-V2's first dense-FFN layer is
  the prelude; RecurrentGemma's (R, R, A) pattern is one scanned
  super-block of three sub-layers; uniform stacks scan super-blocks of 1;
- scanned layer parameters are stacked on a leading dim (compile time
  stays flat in depth) and consumed via ``lax.scan``; caches stack the
  same way;
- ``mode``: train forward (logits), prefill (logits + cache), decode
  (one token + cache update);
- sharding: activation constraints via ``ShardingRules`` (no-op on CPU);
  MoE routed experts run under ``shard_map`` when a mesh is present.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardingRules, make_rules, P
from . import attn as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssd as ssd_mod
from .layers import init_norm, apply_norm, init_gated_mlp, gated_mlp, \
    init_dense

__all__ = ["Model", "build_model", "param_count"]

# ----------------------------------------------------------------- grouping
def layer_groups(cfg: ModelConfig):
    """(prelude_kinds, superblock_kinds, n_scan, postlude_kinds)."""
    pat = list(cfg.block_pattern)
    pre: list[str] = []
    if cfg.moe is not None and cfg.moe.first_dense:
        pre = pat[:cfg.moe.first_dense]
        pat = pat[cfg.moe.first_dense:]
    if cfg.rglru is not None:
        sb = list(cfg.rglru.pattern)
        n_scan = len(pat) // len(sb)
        post = pat[n_scan * len(sb):]
        return pre, sb, n_scan, post
    return pre, pat[:1] if pat else [], len(pat), []


def _layer_is_moe(cfg: ModelConfig, in_prelude: bool) -> bool:
    """MoE applies to scanned layers only (prelude = first_dense layers)."""
    return cfg.moe is not None and not in_prelude


# ------------------------------------------------------------------- blocks
def init_block(key, cfg: ModelConfig, kind: str, moe_layer: bool, dtype):
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if kind == "attn":
        if cfg.mla is not None:
            p["mixer"] = mla_mod.init_mla(ks[0], cfg, dtype)
        else:
            p["mixer"] = attn_mod.init_attn(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_mod.init_rglru(ks[0], cfg, dtype)
    elif kind == "ssd":
        p["mixer"] = ssd_mod.init_ssd(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if kind == "ssd" or cfg.d_ff == 0:
        return p                      # mamba2: mixer-only block
    p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
    if moe_layer:
        p["ffn"] = {"moe": moe_mod.init_moe(ks[1], cfg, dtype)}
        mo = cfg.moe
        if mo.n_shared:
            Fs = (mo.d_shared or mo.d_expert) * mo.n_shared
            kk = jax.random.split(ks[2], 3)
            p["ffn"]["shared"] = {
                "wi": init_dense(kk[0], cfg.d_model, Fs, dtype),
                "wg": init_dense(kk[1], cfg.d_model, Fs, dtype),
                "wo": init_dense(kk[2], Fs, cfg.d_model, dtype,
                                 scale=Fs ** -0.5)}
    else:
        p["ffn"] = {"mlp": init_gated_mlp(ks[1], cfg.d_model, cfg.d_ff,
                                          dtype)}
    return p


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype):
    if kind == "attn":
        if cfg.mla is not None:
            return mla_mod.init_mla_cache(cfg, batch, max_len, dtype)
        window = cfg.rglru.window if cfg.rglru is not None else None
        return attn_mod.init_attn_cache(cfg, batch, max_len, dtype, window)
    if kind == "rglru":
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    if kind == "ssd":
        return ssd_mod.init_ssd_cache(cfg, batch, dtype)
    raise ValueError(kind)


class Model:
    """Functional model: ``init`` → params pytree; ``apply`` per mode."""

    def __init__(self, cfg: ModelConfig, rules: ShardingRules | None = None):
        self.cfg = cfg
        self.rules = rules or make_rules(None)
        self.pre, self.sb, self.n_scan, self.post = layer_groups(cfg)
        self.dtype = jnp.dtype(cfg.param_dtype)
        self.cdtype = jnp.dtype(cfg.compute_dtype)
        self._group_specs_cache = None

    def _group_specs(self):
        """PartitionSpecs of ONE scan group's (unstacked) params."""
        if self._group_specs_cache is None:
            from repro.distributed.sharding import param_pspecs
            moe_layer = _layer_is_moe(self.cfg, in_prelude=False)
            shapes = jax.eval_shape(
                lambda k: [init_block(k, self.cfg, kind, moe_layer,
                                      self.dtype) for kind in self.sb],
                jax.random.key(0))
            self._group_specs_cache = param_pspecs(shapes, self.rules)
        return self._group_specs_cache

    def _pin_group(self, gp):
        """Re-constrain sliced per-layer params to their sharded layout
        inside the scan body — keeps the ZeRO all-gather per-iteration
        instead of letting XLA gather the whole layer stack up front
        (which would materialize every layer's full weights at once)."""
        if self.rules.mesh is None:
            return gp
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(self.rules.mesh, s)),
            gp, self._group_specs(),
            is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------- params
    def init(self, key) -> dict:
        cfg = self.cfg
        dt = self.dtype
        k_embed, k_pre, k_scan, k_post, k_head, k_px = jax.random.split(
            key, 6)
        params: dict = {
            "embed": {"embedding":
                      (jax.random.normal(k_embed,
                                         (cfg.vocab_size, cfg.d_model),
                                         jnp.float32) * 1.0).astype(dt)},
            "final_norm": init_norm(cfg.norm, cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["head"] = {"lm_head":
                              (jax.random.normal(k_head,
                                                 (cfg.d_model,
                                                  cfg.vocab_size),
                                                 jnp.float32)
                               * cfg.d_model ** -0.5).astype(dt)}
        if cfg.input_mode == "tokens+prefix":
            params["prefix"] = {"prefix_proj":
                                init_dense(k_px, cfg.d_model, cfg.d_model,
                                           dt)["w"]}
        if self.pre:
            params["prelude"] = [
                init_block(jax.random.fold_in(k_pre, i), cfg, kind,
                           moe_layer=False, dtype=dt)
                for i, kind in enumerate(self.pre)]
        if self.n_scan:
            moe_layer = _layer_is_moe(cfg, in_prelude=False)

            def one_group(key_i):
                ks = jax.random.split(key_i, len(self.sb))
                return [init_block(ks[j], cfg, kind, moe_layer, dt)
                        for j, kind in enumerate(self.sb)]

            keys = jax.random.split(k_scan, self.n_scan)
            params["scan"] = _stack_groups(
                [one_group(keys[i]) for i in range(self.n_scan)])
        if self.post:
            params["postlude"] = [
                init_block(jax.random.fold_in(k_post, i), cfg, kind,
                           moe_layer=False, dtype=dt)
                for i, kind in enumerate(self.post)]
        return params

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        dt = self.cdtype
        cache: dict = {"len": jnp.zeros((), jnp.int32)}
        if self.pre:
            cache["prelude"] = [init_block_cache(cfg, k, batch, max_len, dt)
                                for k in self.pre]
        if self.n_scan:
            one = [init_block_cache(cfg, k, batch, max_len, dt)
                   for k in self.sb]
            cache["scan"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (self.n_scan,) + x.shape).copy(), one)
        if self.post:
            cache["postlude"] = [init_block_cache(cfg, k, batch, max_len, dt)
                                 for k in self.post]
        return cache

    # -------------------------------------------------------------- apply
    def _block(self, p, x, kind: str, cache, cache_len, moe_layer: bool,
               sp: bool = False):
        cfg, r = self.cfg, self.rules
        seq_ax = "tp" if sp else None
        # §Perf B: decode uses the weight-stationary 2D layout — residual
        # hidden dim sharded over the dp axes so every matmul contracts a
        # sharded dim against the (d, m)-sharded weights: small activation
        # psums instead of per-step weight all-gathers.
        decode2d = (r.mesh is not None and cache is not None
                    and x.shape[1] == 1 and cache_len is not None)

        def res_act(y):
            if decode2d:
                return r.act(y, None, None, "dp")
            return r.act(y, "dp", seq_ax, None)
        h = apply_norm(cfg.norm, p["norm1"], x)
        if sp:
            # Megatron-SP: gather the sequence before the TP projections so
            # GSPMD tensor-parallelizes the matmuls (weights stay sharded)
            # instead of replicating weights against seq-sharded activations
            h = r.act(h, "dp", None, None)
        if kind == "attn":
            if cfg.mla is not None:
                mix, new_cache = mla_mod.mla_block(
                    p["mixer"], h, cfg, cache=cache, cache_len=cache_len)
            else:
                window = cfg.rglru.window if cfg.rglru is not None else None
                mix, new_cache = attn_mod.attn_block(
                    p["mixer"], h, cfg, window=window, cache=cache,
                    cache_len=cache_len,
                    rules=r if r.mesh is not None else None)
        elif kind == "rglru":
            mix, new_cache = rglru_mod.rglru_block(
                p["mixer"], h, cfg, cache=cache, cache_len=cache_len)
        elif kind == "ssd":
            mix, new_cache = ssd_mod.ssd_block(
                p["mixer"], h, cfg, cache=cache, cache_len=cache_len)
        else:
            raise ValueError(kind)
        x = x + mix
        x = res_act(x)
        aux = jnp.zeros((), jnp.float32)
        if "ffn" in p:
            h2 = apply_norm(cfg.norm, p["norm2"], x)
            if sp:
                h2 = r.act(h2, "dp", None, None)
            f = p["ffn"]
            if "moe" in f:
                y, aux = self._moe(f["moe"], h2, decode2d)
                if "shared" in f:
                    y = y + gated_mlp(f["shared"], h2, cfg.act,
                                      rules=r if r.mesh is not None
                                      and not decode2d else None)
            else:
                y = gated_mlp(f["mlp"], h2, cfg.act,
                              rules=r if r.mesh is not None
                              and not decode2d else None)
            x = x + y
            x = res_act(x)
        return x, new_cache, aux

    def _moe(self, p, x, decode2d: bool = False):
        cfg, r = self.cfg, self.rules
        if r.mesh is None:
            return moe_mod.moe_ffn(p, x, cfg, axis_name=None, act=cfg.act)
        dp = r.dp if len(r.dp) > 1 else r.dp[0]
        dp_axes = r.dp

        if decode2d:
            # tokens replicated (tiny at decode), experts stay (E/model,
            # D/data)-sharded; y comes back D-sliced over dp
            def local2d(pp, xx):
                y, aux = moe_mod.moe_ffn(pp, xx, cfg, axis_name="model",
                                         act=cfg.act, axis_data=dp)
                aux = jax.lax.pmean(aux, "model")
                return y, aux

            in_specs = ({"router": {"w": P(None, None)},
                         "wi": P("model", dp, None),
                         "wg": P("model", dp, None),
                         "wo": P("model", None, dp)},
                        P(None, None, None))
            out_specs = (P(None, None, dp), P())
            return jax.shard_map(local2d, mesh=r.mesh, in_specs=in_specs,
                                 out_specs=out_specs)(p, x)

        def local(pp, xx):
            y, aux = moe_mod.moe_ffn(pp, xx, cfg, axis_name="model",
                                     act=cfg.act)
            aux = jax.lax.pmean(aux, dp_axes)
            aux = jax.lax.pmean(aux, "model")
            return y, aux

        in_specs = ({"router": {"w": P(None, None)},
                     "wi": P("model", None, None),
                     "wg": P("model", None, None),
                     "wo": P("model", None, None)},
                    P(dp, None, None))
        out_specs = (P(dp, None, None), P())
        return jax.shard_map(local, mesh=r.mesh, in_specs=in_specs,
                             out_specs=out_specs)(p, x)

    def _embed(self, params, tokens, prefix_embeds=None):
        cfg, r = self.cfg, self.rules
        emb = params["embed"]["embedding"]
        x = jnp.take(emb, tokens, axis=0).astype(self.cdtype)
        if cfg.input_mode == "tokens+prefix" and prefix_embeds is not None:
            px = jnp.einsum("bsd,de->bse",
                            prefix_embeds.astype(self.cdtype),
                            params["prefix"]["prefix_proj"].astype(
                                self.cdtype))
            x = jnp.concatenate([px, x], axis=1)
        elif cfg.input_mode == "embeddings" and prefix_embeds is not None:
            x = prefix_embeds.astype(self.cdtype)
        return r.act(x, "dp", None, None)

    def _head(self, params, x):
        cfg, r = self.cfg, self.rules
        x = apply_norm(cfg.norm, params["final_norm"], x)
        w = (params["embed"]["embedding"].T if cfg.tie_embeddings
             else params["head"]["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return r.act(logits, "dp", None, "tp")

    def _stack_walk(self, params, x, mode: str, cache):
        """Run prelude → scan → postlude.  Returns (x, new_cache, aux)."""
        cfg = self.cfg
        cache_len = cache["len"] if cache is not None else None
        sp = bool(self.rules.sp and self.rules.mesh is not None
                  and mode == "train")
        aux_total = jnp.zeros((), jnp.float32)
        new_cache: dict = {"len": None} if cache is not None else None

        def run_list(blocks, kinds, caches, moe_flags):
            nonlocal aux_total
            nonlocal x
            outs = []
            for i, (p, kind) in enumerate(zip(blocks, kinds)):
                c = caches[i] if caches is not None else None
                x2, nc, aux = self._block(p, x, kind, c, cache_len,
                                          moe_flags, sp)
                x = x2
                aux_total = aux_total + aux
                outs.append(nc)
            return outs

        if self.pre:
            ncs = run_list(params["prelude"], self.pre,
                           cache.get("prelude") if cache else None, False)
            if cache is not None:
                new_cache["prelude"] = ncs

        if self.n_scan:
            moe_layer = _layer_is_moe(cfg, in_prelude=False)
            remat = (mode == "train" and cfg.remat != "none")

            def group_fn(carry, xs):
                xc, aux_c = carry
                gp, gcache = xs
                gp = self._pin_group(gp)
                gnew = []
                for j, kind in enumerate(self.sb):
                    c = gcache[j] if gcache is not None else None
                    xc, nc, aux = self._block(gp[j], xc, kind, c,
                                              cache_len, moe_layer, sp)
                    aux_c = aux_c + aux
                    gnew.append(nc)
                if gcache is None:
                    gnew = None
                return (xc, aux_c), gnew

            f = group_fn
            if remat:
                f = jax.checkpoint(group_fn,
                                   prevent_cse=False,
                                   policy=None)
            xs = (params["scan"],
                  cache.get("scan") if cache is not None else None)
            if cache is None:
                xs = (params["scan"], None)
                (x, aux_total), _ = jax.lax.scan(
                    lambda c, pp: f(c, (pp, None)),
                    (x, aux_total), params["scan"])
            else:
                (x, aux_total), scan_cache = jax.lax.scan(
                    f, (x, aux_total), (params["scan"], cache["scan"]))
                new_cache["scan"] = scan_cache

        if self.post:
            ncs = run_list(params["postlude"], self.post,
                           cache.get("postlude") if cache else None, False)
            if cache is not None:
                new_cache["postlude"] = ncs

        return x, new_cache, aux_total

    # ------------------------------------------------------------ public
    def forward(self, params, tokens, prefix_embeds=None):
        """Train-mode forward: tokens (B, S) → logits (B, S(+px), V)."""
        x = self._embed(params, tokens, prefix_embeds)
        x, _, aux = self._stack_walk(params, x, "train", None)
        return self._head(params, x), aux

    def prefill(self, params, tokens, cache, prefix_embeds=None):
        """Returns (logits_last (B, 1, V), cache')."""
        x = self._embed(params, tokens, prefix_embeds)
        x, new_cache, _ = self._stack_walk(params, x, "prefill", cache)
        new_cache["len"] = cache["len"] + x.shape[1]
        logits = self._head(params, x[:, -1:])
        return logits, new_cache

    def decode_step(self, params, token, cache):
        """token (B,) int32 → (logits (B, 1, V), cache')."""
        x = self._embed(params, token[:, None])
        if self.rules.mesh is not None:
            x = self.rules.act(x, None, None, "dp")     # 2D decode layout
        x, new_cache, _ = self._stack_walk(params, x, "decode", cache)
        new_cache["len"] = cache["len"] + 1
        return self._head(params, x), new_cache


def _stack_groups(groups: list):
    """Stack a list of identical pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *groups)


def build_model(cfg: ModelConfig, rules: ShardingRules | None = None
                ) -> Model:
    return Model(cfg, rules)


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count via eval_shape (no allocation)."""
    model = Model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    total = 0

    def visit(path, leaf):
        nonlocal total
        n = int(np.prod(leaf.shape))
        if active_only and cfg.moe is not None:
            names = [str(getattr(k, "key", "")) for k in path]
            if "moe" in names and names[-1] in ("wi", "wg", "wo"):
                n = n * cfg.moe.top_k // cfg.moe.n_experts
        total += n

    jax.tree_util.tree_map_with_path(visit, shapes)
    return total
