"""Op library: returns/advantage estimators, V-trace, precision policy,
ring attention, and the Pallas TPU kernels (``pallas_*.py``)."""


def pallas_interpret() -> bool:
    """The ``interpret=`` every caller hands a Pallas kernel: compiled by
    Mosaic on the TPU, interpreted everywhere else (how the CPU suite
    validates the kernels). On the TPU there is nothing to fall back to —
    a kernel the chip's compiler refuses fails the program that asked for
    it (tests/test_tpu_compile.py compiles each one for a v5e, and
    chip_smoke.py runs each one compiled against its XLA twin)."""
    import jax

    return jax.default_backend() != "tpu"
