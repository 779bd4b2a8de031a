"""Model FLOPs per token (bench/flops.py) times the tokens per second of
the traced steps, over the chips' bf16 peak (bench/peaks.py), in %."""


def read(run):
    if not run.get("traced_s") or not run["peak_flops"]:
        return None
    rate = run["traced_tokens"] / run["traced_s"]
    return 100.0 * run["flops_per_token"] * rate / (
        run["chips"] * run["peak_flops"])
