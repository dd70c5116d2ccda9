"""Model FLOPs per token (``chipbench/flops.py``, no recomputation) times the
window's tokens per second, over the chips' bf16 peak from
``chipbench/peaks.json``, in percent."""

import json

from chipbench import BENCH
from chipbench.flops import train_flops_per_token


def read(rec):
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = rec["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    flops = train_flops_per_token(rec["config_file"], rec["seq_len"])
    return 100.0 * flops * rec["tokens_per_s"] / (rec["chips"] * peaks[kind]["bf16_flops_per_s"])
