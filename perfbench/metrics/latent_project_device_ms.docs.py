"""latent_project_device_ms.docs: device self-time under the ``latent_project`` scope (q_a / q_b / kv_a, both latent norms, the
rotary, and the absorption ``W_UK^T qn`` and ``W_UV u``), per run of the DECODE program (48 slots)."""

from perfbench import scopes

layer = "latent attention"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("latent_project",), ("decode",))
