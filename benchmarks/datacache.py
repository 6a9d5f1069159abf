"""The binned training set of a cell, kept between runs.

Binning the published tables on the host takes longer than the longest
window, so a run writes the binned ``Dataset`` with the program's own
``save_binary`` to ``benchmarks/.cache/`` and every later run of the
same configuration, row count and seed in that checkout loads it: the
same standing as the compile cache. The file sits at a fixed path
inside the checkout. A header beside it names what it was made from;
a file whose header or contents do not match is rebuilt, never
trusted.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Tuple

# a check runs a cell on several seeds; the oldest files go first once
# the directory holds more than this
CAP_BYTES = 4 << 30


def _evict(cache_dir: str, keep: str) -> None:
    files = [os.path.join(cache_dir, f) for f in os.listdir(cache_dir)
             if f.endswith(".bin")]
    files.sort(key=os.path.getmtime)
    total = sum(os.path.getsize(f) for f in files)
    for path in files:
        if total <= CAP_BYTES:
            break
        if path == keep:
            continue
        total -= os.path.getsize(path)
        for victim in (path, path + ".json"):
            if os.path.exists(victim):
                os.remove(victim)


def binned_dataset(lgb, made_from: Dict[str, Any],
                   dataset_params: Dict[str, Any],
                   make_xy: Callable[[], Tuple[Any, Any]],
                   cache_dir: str):
    """``(dataset, info)``: the constructed ``lgb.Dataset`` for
    ``made_from`` (configuration, generator and its parameters, rows,
    features, seed), loaded from ``cache_dir`` where it holds a
    matching file and built through ``lgb.Dataset`` otherwise. ``info`` says
    which, and how long it took."""
    os.makedirs(cache_dir, exist_ok=True)
    stem = "{config}-{rows}-seed{seed}".format(**made_from)
    path = os.path.join(cache_dir, stem + ".bin")
    want = dict(made_from, dataset_params=dataset_params)
    t0 = time.perf_counter()
    if os.path.exists(path) and os.path.exists(path + ".json"):
        with open(path + ".json") as fh:
            header = json.load(fh)
        if {k: header.get(k) for k in want} == want:
            ds = lgb.Dataset(path, params=dict(dataset_params)).construct()
            inner = ds._inner
            if inner.num_data == made_from["rows"] \
                    and inner.bin_layout_fingerprint() \
                    == header.get("fingerprint") \
                    and float(inner.metadata.label.sum()) \
                    == header.get("label_sum"):
                os.utime(path)
                return ds, {"cache": "hit", "path": path,
                            "seconds": time.perf_counter() - t0}
    x, y = make_xy()
    t_made = time.perf_counter()
    ds = lgb.Dataset(x, label=y, params=dict(dataset_params)).construct()
    ds.data = None          # the raw matrix is not needed again
    t_binned = time.perf_counter()
    tmp = path + ".tmp"
    ds.save_binary(tmp)
    os.replace(tmp, path)
    inner = ds._inner
    with open(path + ".json", "w") as fh:
        json.dump(dict(want, fingerprint=inner.bin_layout_fingerprint(),
                       label_sum=float(inner.metadata.label.sum())), fh)
    _evict(cache_dir, keep=path)
    return ds, {"cache": "miss", "path": path,
                "seconds": time.perf_counter() - t0,
                "generate_s": t_made - t0, "bin_s": t_binned - t_made,
                "save_s": time.perf_counter() - t_binned}
