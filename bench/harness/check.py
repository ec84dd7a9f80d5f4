"""The output check: served greedy tokens against the float32 reference.

For each check request the reference runs once over its prompt and its
served tokens (teacher-forced) and gives, at each position that
produced a served token, the float32 logits.  A served token's gap is
how far its reference logit lies below the reference's best there; the
number compared is the widest gap over every compared token.  A sound
bf16 program picks the reference's best or a near-tie; a wrong cache
entry, page, mask, norm or token lands far below it.

The control puts the reference in the program's place one precision
step down (float8 e4m3 matmuls, see ``reference/qwen3.py``) and reads
the gap of the token that it puts first at the same positions.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from reference import qwen3


def gaps(params, config: dict, prompt: Sequence[int],
         served: Sequence[int], control: bool = False
         ) -> Tuple[np.ndarray, np.ndarray]:
    """(program gaps, control gaps or empty) at each served token."""
    prompt = [int(t) for t in prompt]
    served = [int(t) for t in served]
    n = len(served)
    seq = prompt + served[:-1]
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    ref = np.asarray(qwen3.logits(params, config, seq, rows), np.float64)
    best = ref.max(-1)
    program = best - ref[np.arange(n), served]
    if not control:
        return program, np.zeros(0)
    low = np.asarray(qwen3.logits(params, config, seq, rows, fp8=True))
    picked = low.argmax(-1)
    return program, best - ref[np.arange(n), picked]


def compare(params, config: dict, checks: List[Tuple[Sequence[int],
                                                     Sequence[int]]],
            control: bool = False) -> Dict[str, object]:
    """Widest gaps over every check request; ``checks`` holds
    ``(prompt ids, served ids)``."""
    prog, ctl, per = [], [], []
    for prompt, served in checks:
        p, c = gaps(params, config, prompt, served, control)
        prog.append(p)
        ctl.append(c)
        per.append({"prompt": len(prompt), "served": len(served),
                    "widest_gap": float(p.max()) if p.size else 0.0,
                    "control_gap": float(c.max()) if c.size else None})
    prog = np.concatenate(prog) if prog else np.zeros(0)
    ctl = np.concatenate(ctl) if ctl else np.zeros(0)
    return {"served_gap": float(prog.max()) if prog.size else float("inf"),
            "compared_tokens": int(prog.size),
            "control_gap": float(ctl.max()) if ctl.size else None,
            "requests": per}
