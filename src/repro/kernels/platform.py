"""Where the Pallas kernels run, decided once from the JAX backend.

On ``tpu`` the kernels compile through Mosaic; on ``cpu`` they run in the
Pallas interpreter (tests and CPU runs, ``JAX_PLATFORMS=cpu``).  Any
other backend has no supported kernel path, and asking for one raises
rather than silently interpreting there.
"""

from __future__ import annotations

import jax

__all__ = ["interpret_mode", "kernels_by_default", "mode_by_default"]


def interpret_mode() -> bool:
    """``interpret=`` for every ``pallas_call`` on this process's backend."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the JAX backend here is {platform!r}")


def kernels_by_default() -> bool:
    """``EngineConfig.use_pallas`` when left unset: on for TPU only."""
    return jax.default_backend() == "tpu"


def mode_by_default() -> str:
    """``EngineConfig.mode`` when left unset: ``"dense"`` on TPU, else
    ``"wave"``.

    The wave engine's compacted tail reads SAT corners through XLA
    gathers, which a TPU v5e serializes: about 0.12 G corner reads/s
    (bulk and gather backends alike, one v5e chip), so the paper cascade's
    tail over an 8-frame VGA batch takes minutes there.  Dense waves
    stream every stage over the whole window grid through the Pallas
    kernel instead.  Both modes keep exactly the same survivors.
    """
    return "dense" if jax.default_backend() == "tpu" else "wave"
