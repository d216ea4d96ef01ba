"""The public API (DESIGN.md §10): one call from a declarative config to
a live hybrid-parallel training session.

    from repro.api import RunConfig, compile

    session = compile(RunConfig(model="cosmoflow-512", smoke=True,
                                data=2, spatial=4, global_batch=4))
    print(session.describe())
    loader = session.make_loader()
    loss = session.step(loader.load_batch(ids))

``RunConfig`` subsumes the mesh/plan/precision/grad-comm/opt-state/
checkpoint kwarg threading the drivers used to hand-assemble;
``Session`` lowers to ``repro.train.train_step`` (the internal layer —
deprecated for direct use in drivers, still the substrate the parity
tests pin).

For long campaigns, ``repro.api.supervisor.run(config, steps)`` wraps
the Session in the §11 recovery loop: guarded steps, a step watchdog,
atomic keep-last-K checkpoints, auto-resume from the newest valid one,
and elastic re-planning when the device count shrinks.

Serving (DESIGN.md §15): ``compile(RunConfig(mode="infer"))`` returns a
forward-only ``repro.serve.InferenceSession`` instead — no optimizer
state, restorable straight from training checkpoints —
whose ``.serve()`` starts the batched request harness.
"""
from repro.api import supervisor
from repro.api.config import RunConfig, RunConfigError
from repro.api.session import Report, Session, compile
from repro.api.supervisor import SupervisorReport

__all__ = ["RunConfig", "RunConfigError", "Report", "Session", "compile",
           "supervisor", "SupervisorReport"]
