"""Pallas TPU kernels (flash prefill, fused latent top-k, sparse
reconstruct-attend), their jnp oracles (``ref``) and the backend dispatch
the models call (``ops``)."""
import jax


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs in interpret mode.

    Only the CPU interprets (tests and tiny-size rehearsals); a TPU runs
    the compiled Mosaic kernels.  Any other platform is refused rather
    than silently interpreted — an interpreted kernel on an accelerator
    would measure the interpreter, not the kernel."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                       f"on CPU; platform {platform!r} is neither")
