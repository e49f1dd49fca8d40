"""Engine: device time of the solve mesh's layer merges (the two int32
``psum``s of ``_merge_blocks`` in the program's ``core/lattice.py``,
one all-reduce per layer of the (min,+) sweep) per lattice-program
launch per chip in the traced window, in ms: the module's instructions
whose opcode is ``all-reduce`` (or its ``-start``/``-done`` halves), as
``engine.compiled_hlo_texts()`` gives them.  A chip's all-reduce time
includes its wait for the slowest chip.  These ops also lie under the
``search`` scope, so the time is part of ``search_ms.mesh4``.  None
where the program gives no HLO texts, the trace has no module line, or
no launch inside the window ran an all-reduce."""
from bench import readers


def read(ctx):
    return readers.all_reduce_ms(ctx)
