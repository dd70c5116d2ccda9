"""Model FLOPs per token, as the configuration's reference module counts
them (``train_flops_per_token``, no recomputation), times the window's
tokens per second, over the chips' bf16 peak from ``chipbench/peaks.json``,
in percent."""

import json

from chipbench import BENCH, reference


def read(rec):
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = rec["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    cfg = rec["config_file"]
    flops = reference.load(cfg).train_flops_per_token(cfg, rec["seq_len"])
    return 100.0 * flops * rec["tokens_per_s"] / (rec["chips"] * peaks[kind]["bf16_flops_per_s"])
