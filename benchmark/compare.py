"""Decides ``correct``: every admitted document's written outcome against the
plain reference.

The admitted documents are read back from the generator's shards, the
program's outcomes from the kept and excluded Parquet files it wrote, both
with pyarrow alone.  The reference runs in a pool of worker processes that
import nothing but ``benchmark.reference``.  A document counts as mismatched
when it is missing from both files, written to the wrong one or twice, or
written with other text or metadata than the reference gives; an id the
window never admitted counts too.  The limit is 0.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
from typing import Dict, Iterable, List, Tuple

import yaml

_REF = None


def _init(pipeline: List[Dict], precision: str) -> None:
    global _REF
    from benchmark.reference import Reference

    _REF = Reference(pipeline, precision)


def _run(texts: List[str]):
    return [_REF(t) for t in texts]


def admitted_docs(shard_paths: Iterable[str], n: int) -> Tuple[List[str], List[str]]:
    """The first ``n`` (ids, texts) of the stream, from the shard files."""
    import pyarrow.parquet as pq

    ids: List[str] = []
    texts: List[str] = []
    for path in shard_paths:
        if len(ids) >= n:
            break
        t = pq.read_table(path, columns=["id", "text"])
        ids.extend(t.column("id").to_pylist())
        texts.extend(t.column("text").to_pylist())
    if len(ids) < n:
        raise RuntimeError(f"only {len(ids)} of {n} admitted documents are on disk")
    return ids[:n], texts[:n]


def written(kept_path: str, excluded_path: str) -> Dict[str, List[Tuple[str, str, Dict]]]:
    """id -> every (file, text, metadata) row the program wrote for it."""
    import pyarrow.parquet as pq

    out: Dict[str, List[Tuple[str, str, Dict]]] = {}
    for kind, path in (("kept", kept_path), ("excluded", excluded_path)):
        if not os.path.exists(path):
            continue
        t = pq.read_table(path, columns=["id", "text", "metadata"])
        for i, text, md in zip(t.column("id").to_pylist(), t.column("text").to_pylist(),
                               t.column("metadata").to_pylist()):
            out.setdefault(i, []).append((kind, text, json.loads(md) if md else {}))
    return out


def reference_outcomes(pipeline_yaml: str, texts: List[str], workers: int,
                       precision: str = "float64") -> List[Tuple[str, str, Dict]]:
    """The reference's (file, text, metadata) for each text, in order."""
    with open(pipeline_yaml, encoding="utf-8") as f:
        pipeline = yaml.safe_load(f)["pipeline"]
    if workers <= 1:
        _init(pipeline, precision)
        return _run(texts)
    step = max(1, min(256, len(texts) // (workers * 4) + 1))
    chunks = [texts[i : i + step] for i in range(0, len(texts), step)]
    ctx = mp.get_context("spawn")
    with ctx.Pool(workers, initializer=_init, initargs=(pipeline, precision)) as pool:
        parts = pool.map(_run, chunks)
        pool.close()
        pool.join()
    return [r for part in parts for r in part]


def mismatches(ids: List[str], expected: List[Tuple[str, str, Dict]],
               got: Dict[str, List[Tuple[str, str, Dict]]]) -> Tuple[int, List[str]]:
    """(number of mismatched documents, a few of them described)."""
    bad = 0
    notes: List[str] = []
    admitted = set(ids)
    for i, exp in zip(ids, expected):
        rows = got.get(i, [])
        if len(rows) == 1 and rows[0] == exp:
            continue
        bad += 1
        if len(notes) < 5:
            if not rows:
                notes.append(f"{i}: not written (reference: {exp[0]})")
            elif len(rows) > 1:
                notes.append(f"{i}: written {len(rows)} times")
            else:
                kind, text, md = rows[0]
                diff = sorted(k for k in set(md) | set(exp[2]) if md.get(k) != exp[2].get(k))
                notes.append(
                    f"{i}: program {kind}, reference {exp[0]}; text "
                    f"{'equal' if text == exp[1] else 'differs'}; metadata keys "
                    f"differing {diff[:4]}"
                )
    extra = [i for i in got if i not in admitted]
    bad += len(extra)
    if extra:
        notes.append(f"{len(extra)} written ids were never admitted, e.g. {extra[0]}")
    return bad, notes
