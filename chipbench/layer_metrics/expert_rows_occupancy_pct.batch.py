"""Entries that fell on held experts over the rows the grouped GEMM laid out for them (registry series serving.moe_held_rows / serving.moe_rows_laid_out over the window): how full the expert tiles are that are multiplied."""

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "program_counter"


def read(run):
    reg = run.results.get("registry", {})
    held = reg.get("serving.moe_held_rows")
    laid_out = reg.get("serving.moe_rows_laid_out")
    if not held or not laid_out or not laid_out["sum"]:
        return None
    return 100.0 * held["sum"] / laid_out["sum"]
