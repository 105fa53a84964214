"""Pallas kernels: the iCh-scheduled paper applications (`ich_*`) and the
model kernels the serving stack runs (`flash_attention`, `mamba_scan`)."""


def default_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's `interpret` argument. None picks the Pallas
    interpreter on the CPU backend (the tests force it with
    JAX_PLATFORMS=cpu) and compiled kernels on a TPU; any other backend
    raises, so a run that lost its chip cannot pass on the interpreter."""
    if interpret is not None:
        return bool(interpret)
    import jax
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on a TPU or, interpreted, "
                           f"on the CPU; the default backend is {backend!r}")
    return backend == "cpu"
