"""expert_load_max_over_mean.assist: rows routed to the busiest HELD expert over the mean of the 128 held, from the
``expert_tokens`` counter the programs hand back with a decode tick's tokens (whole run, the eight layers summed).
1.0 is an even load; the grouped matmul's time follows the total, its tail tiles follow this."""

layer = "experts"
unit = "x"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    tokens = (run.get("engine_metrics") or {}).get("expert_tokens")
    if tokens is None or not sum(tokens):
        return None
    return max(tokens) * len(tokens) / sum(tokens)
