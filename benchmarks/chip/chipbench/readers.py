"""Arithmetic that several metric readers share."""

from __future__ import annotations

import re

from chipbench import counts

# the codebook kernel's HLO text: "%codebook_matmul_pallas.7 = f32[M,N]{..}
# custom-call(bf16[M,K]{..} %x, s16[K,N]{..} %w_idx, ...)"
_CALL = re.compile(r"=\s*\w+\[(\d+),(\d+)\][^=]*?custom-call\(\s*\w+\[(\d+),"
                   r"(\d+)\]")


def token_gaps_ms(run) -> list:
    """Every gap between successive output tokens of every request, both
    tokens emitted in the window, in milliseconds."""
    return [(b - a) * 1e3 for r in run.records
            for a, b in zip(r.token_times, r.token_times[1:])
            if run.t_open <= a and b < run.t_close]


def codebook_roofline(run):
    """Least seconds of every codebook-kernel op in the traced window —
    max(FLOPs / peak FLOP/s, bytes / peak bytes/s) per op, from the shapes
    its HLO text states, the index plane at ceil(log2 |W|) bits — over
    those ops' device seconds, in %.  None without a trace, a peak table
    entry, or any such op whose shapes can be read."""
    if run.peaks is None:
        return None
    least = spent = 0.0
    for text, seconds in run.kernel_ops("codebook_matmul_pallas"):
        m = _CALL.search(text)
        if m is None:
            return None
        rows, n, _, k = (int(g) for g in m.groups())
        least += counts.least_seconds(
            *counts.codebook_call(run.shape, rows, k, n), run.peaks)
        spent += seconds
    return 100.0 * least / spent if spent else None


def step_mfu(run):
    """Model FLOPs of the tokens the window's admissions and rounds
    computed — prompt tokens with their causal attention and one head row
    per admission; decoded tokens with attention at their context and a
    head row each — over those calls' host seconds times the peak FLOP/s
    of the cell's chips, in %."""
    if run.peaks is None:
        return None
    flops = seconds = 0.0
    page = run.page_size
    for s in run.window_spans("sched_admit"):
        seconds += s.end - s.start
        if not s.info["chunks"]:
            continue
        n = s.info["prompt_len"]
        first = (-(-n // page) - s.info["chunks"]) * page
        flops += counts.model_flops(
            run.shape, n - first,
            counts.prefill_ctx_sum(n) - counts.prefill_ctx_sum(first),
            heads=1)
    for s in run.window_spans("serve_step"):
        seconds += s.end - s.start
        flops += counts.model_flops(run.shape, s.info["tokens"],
                                    s.info["ctx_sum"],
                                    heads=s.info["tokens"])
    peak = run.cell.chips * run.peaks.flops
    return 100.0 * flops / (seconds * peak) if seconds else None


def collective_share(run):
    """Seconds in which a collective ran on the first device over that
    device's busy seconds in the traced window, in %.  None without a
    trace or where no collective ran."""
    if run.trace is None or not run.trace["collective_s"]:
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["first_busy_s"]
