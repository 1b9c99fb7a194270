"""§Roofline: derive the three roofline terms per (arch × shape × mesh)
from the dry-run records (experiments/dryrun.jsonl).

  compute    = FLOPs_per_chip / peak_FLOP/s
  memory     = bytes_per_chip / HBM_bw
  collective = ici_ring_bytes / ici_bw + dcn_ring_bytes / dcn_bw

(The post-SPMD HLO is the per-device program, so the analyzer's numbers
are already per-chip; multiplying by chips and dividing back per the
assignment formula is an identity.) MODEL_FLOPS uses 6·N·D for train,
2·N·D for prefill, 2·N_active·B for decode (attention-read flops added
for decode cells).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

from repro.configs import SHAPES_BY_NAME, get_config
from repro.launch.mesh import peaks

# the dry-run compiles for the production mesh of TPU v5e chips
HW = peaks("TPU v5 lite")

DRYRUN = Path("experiments/dryrun.jsonl")


def active_params(cfg) -> int:
    """Activated parameter count (MoE: shared + top_k/E of routed)."""
    from repro.models import build_model
    total = build_model(cfg).param_count()
    if cfg.moe is None:
        return total
    m = cfg.moe
    routed_per_layer = m.num_experts * 3 * cfg.d_model * m.d_expert
    routed = cfg.num_layers * routed_per_layer
    active_routed = routed * m.top_k / m.num_experts
    return int(total - routed + active_routed)


def model_flops(cfg, shape) -> float:
    n_act = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    # decode: one token per sequence + attention reads over the cache
    flops = 2.0 * n_act * shape.global_batch
    if cfg.full_attention:
        attn = (4.0 * cfg.num_heads * cfg.head_dim * shape.seq_len
                * cfg.num_layers * shape.global_batch)
        flops += attn
    return flops


def load_records(path: Path = DRYRUN, tag: str = "") -> List[dict]:
    recs = []
    seen = {}
    if not path.exists():
        return recs
    for line in path.read_text().splitlines():
        try:
            r = json.loads(line)
        except json.JSONDecodeError:
            continue
        if r.get("ok") and r.get("tag", "") == tag:
            seen[(r["arch"], r["shape"], r["mesh"])] = r   # last wins
    return list(seen.values())


def terms(rec: dict) -> Dict[str, float]:
    a = rec["analysis"]
    compute = a["flops"] / HW["peak_flops_bf16"]
    memory = a["bytes_accessed"] / HW["hbm_bw"]
    collective = (a["ici_ring_bytes"] / HW["ici_bw"]
                  + a["dcn_ring_bytes"] / HW["dcn_bw"])
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])[0]
    cfg = get_config(rec["arch"])
    shape = SHAPES_BY_NAME[rec["shape"]]
    mf = model_flops(cfg, shape)
    hlo_total = a["flops"] * rec["chips"]
    useful = mf / hlo_total if hlo_total else 0.0
    bound = max(compute, memory, collective)
    mfu = (mf / rec["chips"] / HW["peak_flops_bf16"]) / bound if bound else 0.0
    return {"compute_s": compute, "memory_s": memory,
            "collective_s": collective, "dominant": dominant,
            "model_flops": mf, "useful_flops_ratio": useful,
            "roofline_fraction": mfu}


def table(recs: List[dict]) -> List[str]:
    lines = ["arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
             "useful_ratio,roofline_frac,mem_GiB,mem_GiB_tpu"]
    for r in sorted(recs, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        t = terms(r)
        m = r["memory"]
        lines.append(
            f"{r['arch']},{r['shape']},{r['mesh']},"
            f"{t['compute_s']:.3e},{t['memory_s']:.3e},"
            f"{t['collective_s']:.3e},{t['dominant']},"
            f"{t['useful_flops_ratio']:.3f},{t['roofline_fraction']:.3f},"
            f"{m['total_bytes'] / 2**30:.1f},"
            f"{m['tpu_corrected_bytes'] / 2**30:.1f}")
    return lines


def run() -> list:
    recs = load_records()
    if not recs:
        return ["roofline,0.00,NO dryrun.jsonl found — run "
                "`python -m repro.launch.dryrun --all` first"]
    out = []
    doms = {}
    for r in recs:
        t = terms(r)
        doms[t["dominant"]] = doms.get(t["dominant"], 0) + 1
    out.append(f"roofline_cells,{len(recs)},dominant_terms={doms}")
    # worst roofline fraction (hillclimb candidate #1)
    worst = min(recs, key=lambda r: terms(r)["roofline_fraction"])
    tw = terms(worst)
    out.append(f"roofline_worst_cell,0.00,{worst['arch']}/{worst['shape']}"
               f"/{worst['mesh']} frac={tw['roofline_fraction']:.3f} "
               f"dom={tw['dominant']}")
    most_coll = max(recs, key=lambda r: terms(r)["collective_s"]
                    / max(max(terms(r)["compute_s"],
                              terms(r)["memory_s"]), 1e-12))
    tc = terms(most_coll)
    out.append(f"roofline_most_collective,0.00,{most_coll['arch']}/"
               f"{most_coll['shape']}/{most_coll['mesh']} "
               f"coll={tc['collective_s']:.2e}s")
    return out


if __name__ == "__main__":
    for line in table(load_records()):
        print(line)
