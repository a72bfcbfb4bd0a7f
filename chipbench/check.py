"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample
of the requests the window served, drawn from the seed and always
holding the longest, is run once through the plain float32 reference
over its prompt and its served tokens.  The number compared is the
widest gap by which a served (greedy) token's reference logit lies
below the reference's best at that position.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import reference
from chipbench.traffic import seed_rng


def sample(requests: List[Any], n: int, seed: int) -> List[Any]:
    """``n`` requests with served tokens: the longest, and the rest drawn
    from the seed."""
    reqs = [r for r in requests if len(r.out) >= 2]
    if not reqs:
        return []
    reqs.sort(key=lambda r: (len(r.tokens) + len(r.out), r.rid))
    longest, rest = reqs[-1], reqs[:-1]
    rng = seed_rng(seed, 3)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest and n > 1 else []
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps(flat, m: Dict[str, Any], reqs: List[Any], control: bool = False
         ) -> Dict[str, float]:
    """Widest gap over the sampled requests' served tokens, with the count
    of tokens compared.  With ``control`` the fp8 control takes the
    program's place: ``widest_gap`` is then the gap of the token the
    control puts first at each position, and the program's own reading
    is kept as ``program_widest_gap``."""
    prog, ctrl, n = [], [], 0
    for r in reqs:
        g = reference.served_gaps(flat, m, r.tokens, r.out, control=control)
        prog.append(float(np.max(g["program"])))
        n += len(g["program"])
        if control:
            ctrl.append(float(np.max(g["control"])))
    widest = lambda xs: max(xs) if xs else float("nan")
    if control:
        return {"widest_gap": widest(ctrl), "program_widest_gap": widest(prog),
                "tokens_compared": n}
    return {"widest_gap": widest(prog), "tokens_compared": n}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every compared number within its limit (a NaN never is, and a
    limit not yet set holds nothing)."""
    return all(lim is not None and k in numbers and numbers[k] <= lim
               for k, lim in limits.items())


def describe(numbers: Dict[str, float], limits: Dict[str, float]) -> str:
    return "; ".join(f"{k} {numbers.get(k)!r} (limit {lim!r})"
                     for k, lim in limits.items())
