"""Engine: device self time of the connected C_out program's ``extract``
phase (the extraction scan over the merged value table,
``jax.named_scope("extract")`` in the program's ``core/lattice.py``) per
lattice-program launch per chip in the traced window, in ms.  How ops
are matched to launches and scopes: ``bench/readers.py``.  None where
the program gives no HLO texts, the trace has no module line, or no op
carries a phase scope."""
from bench import readers


def read(ctx):
    return readers.scope_ms(ctx, "extract")
