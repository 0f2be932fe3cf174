"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package provides:
  * ``kernel.py`` — ``pl.pallas_call`` with explicit BlockSpec VMEM tiling,
    written for TPU (MXU-aligned tiles, fp32 accumulation);
  * ``ops.py``    — the public wrapper;
  * ``ref.py``    — the pure-jnp oracle the kernel is validated against.

Every op takes ``interpret`` explicitly, with no default: ``True`` runs
the kernel body in the Pallas interpreter (the CPU tests), ``False``
compiles it for the device.  Nothing picks interpret mode from the
visible backend, so a run on the chip never interprets by accident.
"""
