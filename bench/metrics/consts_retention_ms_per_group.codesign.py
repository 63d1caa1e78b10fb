"""Host time of the retention integral per group-constant evaluation,
from the program's own spans (`RunData.recording`): the
`dse_batch.retention` spans over the `dse_batch.group_constants` spans
(one per topology group and vdd rung). Host clock, traced run; None
where the program records no spans."""


def read(run):
    rec = run.recording
    groups = rec.named("dse_batch.group_constants") if rec is not None \
        else []
    if not groups:
        return None
    ret = rec.named("dse_batch.retention")
    return sum(s.dur for s in ret) / len(groups) * 1e3
