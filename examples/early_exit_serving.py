"""Beyond-paper: cascade early-exit LM serving — the paper's stage-wise
rejection + criticality batching applied to decoder LMs.

    PYTHONPATH=src python examples/early_exit_serving.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models.early_exit import ExitConfig, CascadeBatcher
from repro.serve import make_cascade_decode_step
from repro.compile_cache import use_compile_cache


def main() -> None:
    cfg = get_smoke_config("olmo-1b").with_(n_layers=8)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 8, 16
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))
    cache = model.init_cache(B, 64)
    _, cache = jax.jit(model.prefill)(params, tokens, cache)

    # exits after scan groups 1/3/5 — cascade stages over layer groups
    ecfg = ExitConfig(exit_groups=(1, 3, 5), thresholds=(0.6, 0.5, 0.4))
    step = jax.jit(make_cascade_decode_step(model, ecfg))

    batcher = CascadeBatcher(model.n_scan)
    tok = tokens[:, -1]
    all_depths = []
    for t in range(16):
        tok, cache, depth = step(params, tok, cache)
        all_depths.append(np.asarray(depth))
        for b in range(B):
            batcher.observe(b, float(depth[b]))
    depths = np.stack(all_depths)

    print(f"exit depth (of {model.n_scan} groups): "
          f"mean={depths.mean():.2f}, min={depths.min()}, "
          f"max={depths.max()}")
    print(f"executed fraction (delayed rejection): "
          f"{depths.mean() / model.n_scan:.1%}")
    wave = sum(batcher.group_budget(batcher.bucket(b)) for b in range(B))
    print(f"wave-compaction layer-groups/step: {wave} vs full {B * model.n_scan}"
          f" → modeled compute/energy saving {1 - wave / (B * model.n_scan):.1%}")
    print(f"buckets: {batcher.batches(list(range(B)))}")


if __name__ == "__main__":
    use_compile_cache()
    main()
