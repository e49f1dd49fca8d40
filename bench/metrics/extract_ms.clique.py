"""Engine: device self time of the fused program's ``extract`` phase (the
feasibility pass at the optimum and the extraction scan,
``jax.named_scope("extract")`` in the program's ``core/lattice.py``) per
lattice-program launch in the traced window, in ms.  How ops are matched
to launches and scopes: ``bench/readers.py``.  None where the program
gives no HLO texts, the trace has no module line, or no op carries a
phase scope."""
from bench import readers


def read(ctx):
    return readers.scope_ms(ctx, "extract")
