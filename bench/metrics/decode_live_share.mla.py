"""Share of the slot-steps the decode chunks computed that emitted a
token: the engine's counters `serve.slot_steps_live` over
`serve.slot_steps` (every slot of every step of a chunk, frozen and
empty slots included), from the program's recording of the traced
replays. None where the program has no such counters."""


def read(run):
    rec = run.recording
    if rec is None:
        return None
    c = rec.counters
    if not c.get("serve.slot_steps"):
        return None
    return 100.0 * c["serve.slot_steps_live"] / c["serve.slot_steps"]
