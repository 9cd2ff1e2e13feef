"""Incremental per-file result cache (ISSUE 9 satellite).

The tier-1 repo gate runs mocolint over the whole tree; parsing and
walking ~120 files dominates its ~1 s. As the tree grows that cost grows
linearly — the cache keeps the warm path flat: each file's PER-FILE
results (visit/check_file findings, import edges, suppressions, module
name) are stored under its CONTENT hash, so an unchanged file skips
parse + walk entirely. Cross-file analysis (the R6/R11 boundary walks)
always re-runs, over slim contexts rebuilt from the cached import edges
— a change in module B must still surface a chain finding in untouched
module A, so chain findings are never cached.

Invalidation is hash-of-everything: the cache key folds in the content
hash AND an engine fingerprint covering the mocolint SOURCE itself plus
the active config/rule selection — editing any rule, the config, or the
engine silently invalidates every entry; no version constant to forget
to bump. Entries are one JSON file per source path under
`<cache_dir>/mocolint/` (any directory the caller owns).
"""

from __future__ import annotations

import hashlib
import json
import os

from tools.mocolint.finding import Finding
from tools.mocolint.suppress import Suppression

CACHE_SCHEMA = 1

_FP_CACHE: dict[str, str] = {}


def engine_fingerprint(config, rule_ids) -> str:
    """Hash of everything that can change a per-file verdict besides the
    file itself: the mocolint source tree, the config (scopes, boundaries,
    enabled set), and the active rule selection."""
    key = repr((sorted(rule_ids), config))
    if key in _FP_CACHE:
        return _FP_CACHE[key]
    h = hashlib.sha1()
    h.update(str(CACHE_SCHEMA).encode())
    root = os.path.dirname(os.path.abspath(__file__))
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fname in sorted(filenames):
            if fname.endswith(".py"):
                with open(os.path.join(dirpath, fname), "rb") as f:
                    h.update(f.read())
    h.update(key.encode("utf-8", errors="replace"))
    fp = h.hexdigest()
    _FP_CACHE[key] = fp
    return fp


class SlimContext:
    """A cached file's stand-in for FileContext in cross-file analysis:
    everything finalize()-stage rules read (path/norm/module/imports/
    suppressions), nothing that needs a parse (tree/parents/source)."""

    def __init__(self, path, norm, module, imports, suppressions):
        self.path = path
        self.norm = norm
        self.module = module
        self.imports = imports
        self.suppressions = suppressions


class ResultCache:
    def __init__(self, cache_dir: str):
        self.dir = os.path.join(cache_dir, "mocolint")
        os.makedirs(self.dir, exist_ok=True)

    def _entry_path(self, norm_path: str) -> str:
        name = hashlib.sha1(norm_path.encode("utf-8",
                                             errors="replace")).hexdigest()
        return os.path.join(self.dir, f"{name}.json")

    @staticmethod
    def content_hash(source: str) -> str:
        return hashlib.sha1(source.encode("utf-8",
                                          errors="replace")).hexdigest()

    def load(self, path: str, norm: str, content_hash: str,
             engine_fp: str):
        """(SlimContext, findings) for an unchanged file, else None."""
        try:
            with open(self._entry_path(norm), encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError, ValueError):
            return None
        if (data.get("schema") != CACHE_SCHEMA
                or data.get("hash") != content_hash
                or data.get("engine") != engine_fp):
            return None
        try:
            from tools.mocolint.engine import ImportEdge

            imports = [ImportEdge(**e) for e in data["imports"]]
            sups = [Suppression(line=s["line"], covers=s["covers"],
                                rules=frozenset(s["rules"]))
                    for s in data["suppressions"]]
            findings = [Finding(path=path, **{k: v for k, v in f.items()})
                        for f in data["findings"]]
        except (KeyError, TypeError):
            return None
        ctx = SlimContext(path, norm, data.get("module"), imports, sups)
        return ctx, findings

    def store(self, ctx, findings, content_hash: str,
              engine_fp: str) -> None:
        """Persist one parsed file's per-file results. Findings drop their
        `path` (re-attached at load with the caller's spelling, which the
        shim contract preserves verbatim)."""
        data = {
            "schema": CACHE_SCHEMA,
            "hash": content_hash,
            "engine": engine_fp,
            "module": ctx.module,
            "imports": [
                {"module": e.module, "line": e.line, "lazy": e.lazy,
                 "type_checking": e.type_checking}
                for e in ctx.imports
            ],
            "suppressions": [
                {"line": s.line, "covers": s.covers,
                 "rules": sorted(s.rules)}
                for s in ctx.suppressions
            ],
            "findings": [
                {"line": f.line, "rule": f.rule, "message": f.message,
                 "col": f.col, "severity": f.severity}
                for f in findings
            ],
        }
        tmp = self._entry_path(ctx.norm) + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(data, f)
            os.replace(tmp, self._entry_path(ctx.norm))
        except OSError:
            # a read-only or full cache dir silently degrades to cold runs
            try:
                os.remove(tmp)
            except OSError:
                pass
