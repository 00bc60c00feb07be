# Pallas TPU kernels for the framework's compute hot-spots:
#   flash_attention.py  — fused causal/SWA GQA attention (MXU-tiled)
#   ssd.py              — Mamba2 SSD chunk kernel
#   quant.py            — int8 block quant/dequant (DCN-hop compression)
# ops.py: jit'd dispatch wrappers; ref.py: pure-jnp oracles.

import jax


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.  An explicit
    ``interpret`` wins (the compile-only tests lower for a described TPU
    from a CPU process); None derives it from the platform: interpret
    only on the CPU backend, so on a TPU every kernel compiles."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
