"""Host time of the analytic group constants per transient campaign, from
the program's own spans (`RunData.recording`): the
`dse_batch.group_constants` spans (the scalar electrical constants of
each topology group at the campaign's deck, for the analytic table a
transient sweep also plans) over the `api.run` spans. Host clock,
traced run; None where the program records no spans."""


def read(run):
    rec = run.recording
    runs = rec.named("api.run") if rec is not None else []
    if not runs:
        return None
    consts = rec.named("dse_batch.group_constants")
    return sum(s.dur for s in consts) / len(runs) * 1e3
