"""XLA compiles inside the measured window.

Layer: compilation (JAX and XLA).  Source: a ``jax.monitoring``
listener (``bench/compiles.py``) that counts backend compiles, whether
XLA compiled the program or the persistent cache supplied it.  The
warm-up runs every shape first, so this reads 0 unless the window met
a shape the warm-up did not.  Moves ``stmts_per_s``: a compile stalls
every call queued behind it.
"""


def read(ctx):
    return float(ctx.compiles_in_window)
