"""Production inference serving for 3D volumes (DESIGN.md §15).

One serving entry point: ``InferenceSession`` (forward-only sessions
compiled from ``RunConfig(mode="infer")`` or restored from training
checkpoints) and ``ServingHarness`` (the batched request queue its
``.serve()`` starts).
"""
from repro.serve.harness import ServingHarness
from repro.serve.session import InferenceSession, InferReport, compile_infer

__all__ = ["InferenceSession", "InferReport", "ServingHarness",
           "compile_infer"]
