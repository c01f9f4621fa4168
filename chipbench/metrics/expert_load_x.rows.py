"""The most loaded expert's assignments over the mean expert's, per batch
and MoE layer, summed over the window's queries before the ratio: 1 is an
even spread, and the kernel's time follows the tiles of the fullest
experts."""
from chipbench import program


def read(run):
    experts = run.work.get("experts")
    assignments = program.predicate_total(run, "expert_assignments")
    if not experts or assignments is None:
        return None
    return program.ratio(program.predicate_total(run, "expert_load_peak"),
                         assignments / experts)
