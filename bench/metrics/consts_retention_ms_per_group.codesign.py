"""Host time of the retention integral per group-constant evaluation,
from the program's own spans (`bench.lib.program`): the
`dse_batch.retention` spans over the `dse_batch.group_constants` spans
(one per topology group and vdd rung). Host clock, traced run; None
where the program records no spans."""
from bench.lib import program

program.record()


def read(run):
    rec = program.window(run)
    groups = rec.named("dse_batch.group_constants") if rec is not None \
        else []
    if not groups:
        return None
    ret = rec.named("dse_batch.retention")
    return sum(s.dur for s in ret) / len(groups) * 1e3
