"""latent_attend_device_ms.docs: device self-time under the ``latent_attend`` scope (the absorbed walk: every slot's latent pages
gathered a block at a time up to the longest live context, scored whole and summed), per run of the DECODE program (48 slots,
the 8 layers summed)."""

from perfbench import scopes

layer = "latent attention"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("latent_attend",), ("decode",))
