"""Share of the (q block, k block) grid points a window launch visits
that hold a live pair, from the program's own census of the launch
(``ops.pallas_attention.block_census(..., window=...)``, which counts
from the predicate the kernels run): the runner reads it for the cell's
sequence and window and hands it on as telemetry
(``swa_blocks``: ``visited``, ``live``, ``executed``, ``below_window``).
Counted from shapes, so a rehearsal could read it too; it is left out
there as every share is."""


def read(ctx):
    blocks = (ctx.telemetry or {}).get("swa_blocks")
    if ctx.spec.rehearse or not blocks or not blocks["visited"]:
        return None
    return 100.0 * blocks["live"] / blocks["visited"]
