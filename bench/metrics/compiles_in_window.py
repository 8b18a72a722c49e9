"""Executables built inside the measured window: misses of the program's
``ExecutableCache`` plus backend compiles seen by the ``jax.monitoring``
listener (``bench/lib/compiles.py``).  A miss that compiles counts on both;
the expected value is 0.  Split by the end-to-end metric it moves
(``compiles_in_window.bulk``, ``.poisson``); every split reads this."""


def read(ctx):
    return float(ctx.counters["exec_cache_compiles"] + ctx.counters["backend_compiles"])
