"""Generate on-disk datasets for the quickstart example and benchmarks.

Two generators, both writing through the storage subsystem
(``repro.io``, DESIGN.md §5):

  * :func:`make_events_dataset` — an "events" fact table (6 columns, with
    ``day`` sorted so date-range predicates prune whole fragments) plus a
    "users" dimension table, the classic scan→join→groupby shape.  Used
    by ``examples/quickstart.py`` and ``benchmarks/run.py``'s
    ``ingest_scan_*`` cases.
  * :func:`make_corpus_dataset` — the synthetic training corpus
    (docs + tokens) as datasets, feeding ``repro.data.pipeline.disk_corpus``.

Run:  PYTHONPATH=src python scripts/make_dataset.py events /tmp/events_ds
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))


def make_events_arrays(n_rows: int = 100_000, n_users: int = 1_000,
                       n_days: int = 30, seed: int = 0):
    """The events fact table and users dimension table as numpy columns:
    ``(events, users)``.  Events are sorted by ``day``; ``users.user_id``
    is ``arange(n_users)``."""
    rng = np.random.default_rng(seed)
    events = {
        "user_id": rng.integers(0, n_users, n_rows).astype(np.int32),
        "day": np.sort(rng.integers(0, n_days, n_rows)).astype(np.int32),
        "value": rng.normal(size=n_rows).astype(np.float32),
        "score": rng.uniform(0, 1, n_rows).astype(np.float32),
        "clicks": rng.integers(0, 20, n_rows).astype(np.int32),
        "flag": (rng.uniform(size=n_rows) < 0.3),
    }
    users = {
        "user_id": np.arange(n_users, dtype=np.int32),
        "segment": rng.integers(0, 8, n_users).astype(np.int32),
        "weight": rng.uniform(0.5, 2.0, n_users).astype(np.float32),
    }
    return events, users


def write_events_dataset(root: str, events, users, fmt=None,
                         rows_per_group: int = None) -> str:
    """Write :func:`make_events_arrays` output as ``root/events`` and
    ``root/users`` datasets."""
    from repro.io import write_dataset

    n_rows = events["user_id"].shape[0]
    per = rows_per_group or max(n_rows // 16, 1)
    write_dataset(os.path.join(root, "events"), [(events, n_rows)],
                  format=fmt, rows_per_group=per)
    write_dataset(os.path.join(root, "users"),
                  [(users, users["user_id"].shape[0])], format=fmt)
    return root


def make_events_dataset(root: str, n_rows: int = 100_000,
                        n_users: int = 1_000, n_days: int = 30,
                        fmt=None, rows_per_group: int = None,
                        seed: int = 0) -> str:
    """Events fact table + users dimension table under ``root``.

    Events are sorted by ``day`` so per-fragment min/max statistics make
    day-range predicates prunable — the pushdown demo/benchmark shape.
    """
    events, users = make_events_arrays(n_rows, n_users, n_days, seed)
    return write_events_dataset(root, events, users, fmt=fmt,
                                rows_per_group=rows_per_group)


def make_corpus_dataset(root: str, n_docs: int = 64, mean_doc_len: int = 96,
                        vocab_size: int = 128, fmt=None,
                        seed: int = 0) -> str:
    """The training corpus (docs + tokens) as on-disk datasets."""
    from repro.data.pipeline import CorpusConfig, synthetic_corpus_arrays
    from repro.io import write_dataset

    arrays = synthetic_corpus_arrays(CorpusConfig(
        n_docs=n_docs, mean_doc_len=mean_doc_len, vocab_size=vocab_size,
        seed=seed))
    for name, cols in arrays.items():
        n = next(iter(cols.values())).shape[0]
        write_dataset(os.path.join(root, name), [(cols, n)], format=fmt,
                      rows_per_group=max(n // 8, 1))
    return root


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("kind", choices=("events", "corpus"))
    p.add_argument("root")
    p.add_argument("--rows", type=int, default=100_000)
    p.add_argument("--format", default=None,
                   help="hpt | parquet | auto (default: parquet when "
                        "pyarrow is available, else hpt)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.kind == "events":
        make_events_dataset(args.root, n_rows=args.rows, fmt=args.format,
                            seed=args.seed)
    else:
        make_corpus_dataset(args.root, fmt=args.format, seed=args.seed)
    print(f"wrote {args.kind} dataset(s) under {args.root}")


if __name__ == "__main__":
    main()
