"""Validate BENCH_*.json payloads against benchmarks/bench_schema.json.

The benchmark payloads are the repo's published evidence — downstream
tooling (``python -m repro.obs.report``, the README tables, CI trend
diffing) reads them by key, so a silently renamed or dropped field is a
regression even when every gate still passes.  This checker pins the
shapes: it implements the small JSON-Schema subset the schema file uses
(``type`` / ``required`` / ``properties`` / ``items`` / ``enum`` plus a
local ``$arm`` reference for the serve arms), deliberately avoiding a
``jsonschema`` dependency.

``required`` lists only keys common to quick (CI smoke) and full runs;
full-only extras (hier-row fields) are validated when
present.  JSON has one number type, so ``number`` accepts ints while
``integer`` rejects floats with a fractional part.

Usage::

    python benchmarks/check_bench_schema.py FILE [FILE ...]

Each FILE's schema is chosen by its top-level ``benchmark`` key.  Exit
status is non-zero if any file fails, with one line per violation.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, List

SCHEMA_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_schema.json")

_TYPES = {
    "object": dict,
    "array": list,
    "string": str,
    "boolean": bool,
    "integer": int,
    "number": (int, float),
}


def _type_ok(value: Any, tname: str) -> bool:
    py = _TYPES[tname]
    if isinstance(value, bool) and tname in ("integer", "number"):
        return False  # bool is an int subclass; schemas mean real numbers
    if tname == "integer" and isinstance(value, float):
        return float(value).is_integer()
    return isinstance(value, py)


def validate(value: Any, schema: dict, schemas: dict, path: str,
             errors: List[str]) -> None:
    """Append one error line per violation under ``path``."""
    if schema.get("$arm"):
        schema = schemas["$arm"]
    tname = schema.get("type")
    if tname is not None and not _type_ok(value, tname):
        errors.append(f"{path}: expected {tname}, got "
                      f"{type(value).__name__} ({value!r:.60})")
        return
    if "enum" in schema and value not in schema["enum"]:
        errors.append(f"{path}: {value!r} not in {schema['enum']}")
    if isinstance(value, dict):
        for key in schema.get("required", []):
            if key not in value:
                errors.append(f"{path}: missing required key {key!r}")
        props = schema.get("properties", {})
        for key, sub in props.items():
            if key in value:
                validate(value[key], sub, schemas, f"{path}.{key}", errors)
    elif isinstance(value, list) and "items" in schema:
        for i, item in enumerate(value):
            validate(item, schema["items"], schemas, f"{path}[{i}]", errors)


def check_file(path: str, schemas: dict) -> List[str]:
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable or not JSON: {e}"]
    if not isinstance(payload, dict) or "benchmark" not in payload:
        return [f"{path}: no top-level 'benchmark' key"]
    name = payload["benchmark"]
    schema = schemas.get(name)
    if schema is None:
        return [f"{path}: unknown benchmark {name!r} "
                f"(schema knows {sorted(k for k in schemas if not k.startswith('$'))})"]
    errors: List[str] = []
    validate(payload, schema, schemas, name, errors)
    return errors


def main(argv=None) -> int:
    files = (argv if argv is not None else sys.argv[1:])
    if not files:
        print(__doc__)
        return 2
    with open(SCHEMA_PATH) as f:
        schemas = json.load(f)["benchmarks"]
    rc = 0
    for path in files:
        errors = check_file(path, schemas)
        if errors:
            rc = 1
            print(f"FAIL {path}")
            for e in errors:
                print(f"  {e}")
        else:
            print(f"OK   {path}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
