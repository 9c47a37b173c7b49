"""``--tiny-cpu``: the same cell at a size the sandbox's CPU can run.

Only for rehearsing the harness without a chip (control flow, file
look-up, the result line). It rewrites the loaded cell in memory —
widths, depth, batch, slots, lengths, rates — and touches no file. A
run made this way prints no device metric (run.py).
"""

from __future__ import annotations

from typing import Any, Dict

MODEL = {"n_embd": 128, "n_layer": 2, "n_head": 2, "n_positions": 128,
         "n_ctx": 128, "vocab_size": 500}
PADDED_VOCAB = 512


def _shrink_lengths(spec: Dict[str, Any], cap: int) -> Dict[str, Any]:
    """Lengths divided by 16 and held inside ``[2, cap]``."""
    out = dict(spec)
    if spec["dist"] == "mixture":
        out["parts"] = [_shrink_lengths(p, cap) for p in spec["parts"]]
        return out
    for key in ("median", "min", "max", "value"):
        if key in out:
            out[key] = max(2, min(cap, int(out[key]) // 16))
    return out


def shrink(cell: Dict[str, Any]) -> None:
    cell["config"] = dict(cell["config"], **MODEL)
    cell["config"]["assumed"] = dict(cell["config"]["assumed"],
                                     padded_vocab_size=PADDED_VOCAB)
    rec = cell["recipe"] = dict(cell["recipe"])
    tr = cell["traffic"] = dict(cell["traffic"])
    if "batch" in rec:
        rec["batch"] = 2 * rec["mesh"]["dp"]
        rec["model_pins"] = dict(rec.get("model_pins", {}))
        if "ce_chunk" in rec["model_pins"]:
            rec["model_pins"]["ce_chunk"] = 64
        tr["seq_len"] = MODEL["n_positions"]
        tr["data"] = dict(tr["data"], records=64)
    if "engine" in rec:
        rec["engine"] = dict(rec["engine"], slots=4, max_prompt_len=32,
                             max_seq_len=64, decode_chunk=4,
                             prompt_buckets=[16, 32],
                             admit_batch_sizes=[1, 2])
        # the Pallas decode kernel, interpreted, so that the path the
        # chip runs is the path rehearsed
        rec["model_pins"] = dict(rec.get("model_pins", {}),
                                 decode_attn_impl="kernel")
        tr["prompt_len"] = _shrink_lengths(tr["prompt_len"], 32)
        tr["output_len"] = _shrink_lengths(tr["output_len"], 16)
        tr["ramp_s"] = 1.0
        if "arrivals" in tr:
            tr["arrivals"] = dict(tr["arrivals"], rate_per_s=6.0)
