"""Model FLOP utilization of the traced sub-window: the operations the
model needs for the tokens the engine processed there (decode tokens
with their attention span and logits, prefill chunk tokens with causal
attention, one logits row per finished prompt; the architecture
module's counts, ``run.arch``) over the sub-window's length times the
peak bf16 rate of every chip the cell holds, in percent."""


def read(run):
    if run.trace is None or run.trace_host is None:
        return None
    total = 0
    for s in run.traced_steps():
        total += sum(run.arch.decode_token_flops(run.dm, c)
                     for c in s.decode_ctx)
        total += sum(run.arch.prefill_chunk_flops(run.dm, a, n, end)
                     for a, n, end in s.prefill)
    span = run.trace_host[1] - run.trace_host[0]
    chips = run.cell.get("chips", 1)
    return 100.0 * total / (span * chips * run.peak["bf16_flops"])
