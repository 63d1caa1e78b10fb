"""The program entry points the benchmark wraps in spans, by layer, and
the counts readers take from those spans. A span's name is its layer's
module path and the function's name; the trace shows it as
`bench.<name>`."""

# core/spice/char_batch: one call per transient campaign (host netlist
# and stimulus prep, the lattice program, crossing extraction)
CHARACTERIZE = {"module": "repro.core.spice.char_batch",
                "attr": "characterize", "span": "char_batch.characterize",
                "shapes": True}
# the fused Newton lattice program, blocked on its result in the span
RUN_LATTICE = {"module": "repro.core.spice.transient",
               "attr": "Transient.run_lattice",
               "span": "transient.run_lattice", "block": True,
               "shapes": True}
# core/dse_batch: host constants per (topology group, vdd rung), the
# (vdd x lattice) evaluation, the co-design cube, points and shmoo grids
GROUP_CONSTANTS = {"module": "repro.core.dse_batch",
                   "attr": "_group_constants",
                   "span": "dse_batch.group_constants"}
VDD_LATTICE = {"module": "repro.core.dse_batch",
               "attr": "evaluate_vdd_lattice",
               "span": "dse_batch.evaluate_vdd_lattice"}
CODESIGN_METRICS = {"module": "repro.core.dse_batch",
                    "attr": "codesign_metrics",
                    "span": "dse_batch.codesign_metrics"}
EVALUATE_BATCH = {"module": "repro.core.dse_batch",
                  "attr": "evaluate_batch",
                  "span": "dse_batch.evaluate_batch"}
SHMOO_BATCH = {"module": "repro.core.dse_batch", "attr": "shmoo_batch",
               "span": "dse_batch.shmoo_batch"}
# api: the session entry
SESSION_RUN = {"module": "repro.api.session", "attr": "Session.run",
               "span": "api.Session.run"}
DSE_BATCH = (VDD_LATTICE, CODESIGN_METRICS, EVALUATE_BATCH, SHMOO_BATCH)


def real_points(run) -> int:
    """Design points handed to `characterize` in the window."""
    return sum(s.info["args"][0]["len"]
               for s in run.spans.named(CHARACTERIZE["span"]))


def lanes(run) -> int:
    """Lanes the lattice program computed: its batch, padding included
    (the stop-time operand is (B,))."""
    return sum(s.info["args"][3][0]
               for s in run.spans.named(RUN_LATTICE["span"]))
# serving/engine: one drained replay (admissions, decode chunks, the
# host's reconcile between them); api: the measured co-design after it
SERVE_RUN = {"module": "repro.serving.engine", "attr": "ServeEngine.run",
             "span": "engine.ServeEngine.run"}
CODESIGN_MEASURED = {"module": "repro.api.session",
                     "attr": "Session.codesign_measured",
                     "span": "api.Session.codesign_measured"}
# device programs of the serving engine, by the module names the trace
# gives the engine's jitted admission and decode-chunk functions
ADMIT_MODULE = "jit__admit_kernel"
CHUNK_MODULE = "jit__chunk"


def served(run):
    """(replay record, prompt length, answer length) of every engine
    request in the replays completed while traced."""
    return [(r, p, o) for r in run.traced if r.ok
            for _, p, o in r.request["prompts"]]
