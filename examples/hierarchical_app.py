"""Hierarchical (IMS-style) read (reference SparkCobolHierarchical.scala):
7 segment types assembled into nested parent/child rows
(TestDataGen17Hierarchical data)."""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cobrix_tpu import read_cobol
from cobrix_tpu.explain import explain
from cobrix_tpu.testing.generators import (HIERARCHICAL_COPYBOOK,
                                           HIERARCHICAL_PARENT_MAP,
                                           HIERARCHICAL_SEGMENT_MAP,
                                           generate_hierarchical)


def main():
    raw = generate_hierarchical(20, seed=100)
    seg_opts = {f"redefine_segment_id_map:{i}": f"{name} => {sid}"
                for i, (sid, name) in enumerate(
                    HIERARCHICAL_SEGMENT_MAP.items())}
    child_opts = {f"segment-children:{i}": f"{parent} => {child}"
                  for i, (child, parent) in enumerate(
                      HIERARCHICAL_PARENT_MAP.items())}
    with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
        f.write(raw)
        path = f.name
    options = dict(copybook_contents=HIERARCHICAL_COPYBOOK,
                   is_record_sequence="true", segment_field="SEGMENT-ID",
                   **seg_opts, **child_opts)
    # before any data is read: assembled in columns, or by a record walk
    plan = explain(**options).plan
    print("route:", plan["hierarchical"],
          plan.get("hierarchical_reason", ""))
    try:
        result = read_cobol(path, **options)
        table = result.to_arrow()   # one nested row a root record
        rows = result.to_rows()
    finally:
        os.unlink(path)
    print(f"{len(rows)} assembled company trees")
    first = rows[0][0]  # the ENTITY root record of the first row
    print("first company fields:", first[:2])
    print("nested columns:", table.schema.names)
    # what the read did: roots, records, children put under a parent,
    # orphans, and the roots a record walk assembled (0 on this route)
    print("counts:", result.metrics.as_dict()["hier"])


if __name__ == "__main__":
    main()
