"""Useful model FLOPs of the traced part over the chip's bf16 peak (%):
prompts prefilled for admitted requests and tokens decoded."""

from bench import counts


def read(run):
    calls = run.traced_calls()
    if not calls:
        return None
    flops = counts.serve_useful_flops(run.config, calls,
                                      run.counts["prompt_len"])
    window = run.traced[1] - run.traced[0]
    return 100.0 * flops / window / run.peaks["bf16_flops"]
