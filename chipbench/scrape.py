"""Read the program's Prometheus exposition (``GET /v1/metrics``) and
difference two scrapes over the measured window."""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

Labels = Tuple[Tuple[str, str], ...]
_LINE = re.compile(r'^([A-Za-z_:][A-Za-z0-9_:]*)(\{(.*)\})?\s+(\S+)$')
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> Dict[str, Dict[Labels, float]]:
    """``{series name: {sorted (label, value) pairs: number}}``."""
    out: Dict[str, Dict[Labels, float]] = {}
    for line in text.splitlines():
        m = _LINE.match(line.strip())
        if not m or line.startswith("#"):
            continue
        name, _, raw, value = m.groups()
        labels = tuple(sorted(_LABEL.findall(raw or "")))
        out.setdefault(name, {})[labels] = float(value)
    return out


def total(scrape: Dict[str, Dict[Labels, float]], name: str,
          **match: str) -> float:
    """Sum of a series over every label set that holds ``match``."""
    want = set(match.items())
    return sum(v for labels, v in scrape.get(name, {}).items()
               if want <= set(labels))


def window_mean(before, after, histogram: str, **match: str
                ) -> Optional[float]:
    """Mean of a histogram's observations made between two scrapes, in
    its own unit; None where none was made."""
    n = (total(after, histogram + "_count", **match)
         - total(before, histogram + "_count", **match))
    if n <= 0:
        return None
    return (total(after, histogram + "_sum", **match)
            - total(before, histogram + "_sum", **match)) / n
