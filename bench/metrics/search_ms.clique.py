"""Engine: device self time of the fused program's ``search`` phase (the
gate builder and the threshold search loop, ``jax.named_scope("search")``
in the program's ``core/lattice.py``) per lattice-program launch in the
traced window, in ms.  How ops are matched to launches and scopes:
``bench/readers.py``.  None where the program gives no HLO texts, the
trace has no module line, or no op carries a phase scope."""
from bench import readers


def read(ctx):
    return readers.scope_ms(ctx, "search")
