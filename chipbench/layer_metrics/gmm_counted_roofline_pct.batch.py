"""The whole-bank grouped GEMM's share of its roofline with the rows the program counted: the calls kernels/grouped_matmul.py matches, priced by kernels/grouped_matmul_held.py's cost from the registry series serving.moe_held_rows (where every expert is held: the entries of the rows that hold a token), never from the operand's M, which is the row bucket's."""
from chipbench.harness import readers
from chipbench.harness.spec import load_module

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    """One observation of the series is one step's entries summed over the
    layers that have experts (all but the configuration's leading dense
    ones, as run); a layer makes three calls, each over its own entries:
    ``2 rows K N`` operations, ``E K N + rows (K + N)`` numbers moved."""
    d = run.results.get("registry", {}).get("serving.moe_held_rows")
    if not d or not d["count"]:
        return None
    dense = run.cell.config.get("layer_pattern", {}).get("leading_dense", 0)
    rows = d["sum"] / d["count"] / (run.model["num_hidden_layers"] - dense)
    cost = load_module(run.cell.root, "kernels", "grouped_matmul_held").cost
    return readers.kernel_roofline_pct(
        run, "grouped_matmul", lambda mod, shapes: cost(shapes, rows))
