"""Rows the expert kernel computed (each expert's last tile in full) over
the real-token assignments dispatched to it, over the window's queries."""
from chipbench import program


def read(run):
    return program.ratio(program.predicate_total(run, "expert_rows_launched"),
                         program.predicate_total(run, "expert_assignments"))
