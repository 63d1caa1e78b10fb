"""Host prep of the transient characterization per real point, from the
program's own spans (`RunData.recording`): the self time of
`char_batch.prep` (banks, netlists, analytic estimates, stimulus, G/C
assembly, bucket padding; less any program span below it) over the
counter `char_batch.points`. Host clock, traced run; None where the
program records no spans."""


def read(run):
    rec = run.recording
    n = rec.counters["char_batch.points"] if rec is not None else 0
    if not n:
        return None
    prep = {s.id for s in rec.named("char_batch.prep")}
    below = {s.name for s in rec.spans if s.parent in prep}
    return rec.self_time("char_batch.prep", below) / n * 1e3
