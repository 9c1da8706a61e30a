"""From the program's per-request stamp tables to window metrics.

The program writes one whitespace table per final-stage instance
(``logs/<job>/<device>-group<g>-<i>.txt``): a header of event names,
one row of epoch stamps per completed request, ``#`` trailers. A row
carries no request id, so rows are matched to the schedule by the
``enqueue_filename`` stamp, which the client takes just after the
iterator hands it the file: request *i* is the one whose ``sent``
stamp is the latest not after it.

Percentiles, window selection and rates are computed here, on the
benchmark's side (the percentile is a copy of
``rnb_tpu.telemetry.latency_percentiles``: numpy's linear
interpolation).
"""

from __future__ import annotations

import bisect
import os
from typing import Dict, List, Sequence

import numpy as np


def percentile(values: Sequence[float], p: float) -> float:
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), p))


def read_tables(log_dir: str) -> Dict[str, List[dict]]:
    """{instance name: [{event: stamp}]} for every final-stage table."""
    tables = {}
    for name in sorted(os.listdir(log_dir)):
        if "-group" not in name or not name.endswith(".txt"):
            continue
        rows = []
        with open(os.path.join(log_dir, name)) as f:
            header = None
            for line in f:
                if not line.strip() or line.startswith("#"):
                    continue
                if header is None:
                    header = line.split()
                    continue
                fields = line.split()
                rows.append({key: float(fields[i])
                             for i, key in enumerate(header)
                             if not key.startswith("device")})
        tables[name[:-4]] = rows
    return tables


def finish_key(row: dict) -> str:
    """The last stage's finish event: the highest ``inference<k>_finish``."""
    keys = [k for k in row if k.startswith("inference")
            and k.endswith("_finish")]
    if not keys:
        raise ValueError("a stamp row has no inference*_finish event: %s"
                         % sorted(row))
    return max(keys, key=lambda k: int(k[len("inference"):-len("_finish")]))


def match_requests(tables: Dict[str, List[dict]], sent: np.ndarray):
    """-> (finish, instance): per scheduled request its finish epoch
    (NaN where it never completed) and the instance that finished it."""
    finish = np.full(len(sent), np.nan)
    instance = [None] * len(sent)
    order = [float(s) for s in sent if not np.isnan(s)]
    for name, rows in tables.items():
        last = finish_key(rows[0]) if rows else None
        for row in rows:
            i = bisect.bisect_right(order, row["enqueue_filename"]) - 1
            if i < 0:
                raise ValueError("a completed request was enqueued "
                                 "before the first one was sent")
            if not np.isnan(finish[i]):
                raise ValueError("two completed rows match request %d: "
                                 "stamps too close to tell apart" % i)
            finish[i] = row[last]
            instance[i] = name
    return finish, instance


def in_window(stamps: np.ndarray, window) -> np.ndarray:
    """Mask of stamps inside [start, end); NaN is outside."""
    with np.errstate(invalid="ignore"):
        return (stamps >= window[0]) & (stamps < window[1])
