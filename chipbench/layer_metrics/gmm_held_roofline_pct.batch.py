"""The grouped GEMM's share of its roofline where the chip holds a share of the experts: rows priced from the program's own count of entries on held experts (registry series serving.moe_held_rows), never from the operand's M (kernels/grouped_matmul_held.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    """One observation of the series is one step's entries summed over the
    layers that have experts (all but the configuration's leading dense
    ones, as run); a layer makes three calls, each over its own entries."""
    d = run.results.get("registry", {}).get("serving.moe_held_rows")
    if not d or not d["count"]:
        return None
    dense = run.cell.config.get("layer_pattern", {}).get("leading_dense", 0)
    rows = d["sum"] / d["count"] / (run.model["num_hidden_layers"] - dense)
    return readers.kernel_roofline_pct(
        run, "grouped_matmul_held", lambda mod, shapes: mod.cost(shapes, rows))
