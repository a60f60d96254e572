"""Model FLOP/s over the peak of the cell's chips, in %: rows per second
times the operations of one row as the model family counts them
(``models/<family>.ops``; for ``mlp4bit`` 2 * sum K*N), over chips times
the peak of the configuration's activation type (int8 or bf16)."""
from harness import costs


def read(r):
    return costs.mfu(r.window["rows_per_s"], r.family.ops(r.shapes, 1),
                     r.chips, r.device_kind, r.act_dtype)
