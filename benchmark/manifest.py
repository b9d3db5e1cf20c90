"""BENCHMARK.json and the files it names: loading, and the checks of
``--validate``. Everything that belongs to one configuration, one cell or one
metric is found here by its name, so a later PR adds files and entries and
edits none."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
#: where a configuration's tables come from (its file's ``tables_from``):
#: ``createDataFrame`` of the Arrow tables, or parquet files written in
#: set-up and read by ``session.read.parquet``
TABLES_FROM = ("memory", "parquet")


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names breaks the contract."""


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load(path=None):
    return _read_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def config_file(manifest, name):
    for c in manifest["configs"]:
        if c["name"] == name:
            return _read_json(os.path.join(ROOT, c["file"]))
    raise ManifestError(f"no configuration {name!r}")


def tables_from(config):
    """Where the configuration's tables come from; ``memory`` unless its
    file says otherwise."""
    return config.get("tables_from", TABLES_FROM[0])


def config_problems(name, config):
    """Breaches in what a configuration's file states for the harness."""
    bad = []
    if tables_from(config) not in TABLES_FROM:
        bad.append(f"configuration {name!r}: tables_from "
                   f"{config['tables_from']!r} is none of {TABLES_FROM}")
    sf = config.get("scale_factor")
    if isinstance(sf, bool) or not isinstance(sf, (int, float)) or not sf > 0:
        bad.append(f"configuration {name!r}: scale_factor {sf!r} is not a "
                   "positive number")
    return bad


def workload_entry(manifest, name):
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json")


def workload_file(name):
    return _read_json(os.path.join(HERE, "workloads", f"{name}.json"))


def metric_file(name):
    return _read_json(os.path.join(HERE, "metrics", f"{name}.json"))


def query_sql(query_id):
    with open(os.path.join(HERE, "queries", f"{query_id}.sql")) as f:
        return f.read()


def tables_named(query_ids, schema):
    """The tables of ``schema`` that the queries' SQL text names."""
    words = set()
    for qid in query_ids:
        words |= set(re.findall(r"[a-z_][a-z_0-9]*", query_sql(qid).lower()))
    return [t for t in schema if t in words]


def peaks():
    return _read_json(os.path.join(HERE, "peaks.json"))


def metrics_of(manifest, cell, group):
    """The metrics of ``group`` (``end_to_end`` | ``per_layer``) that the
    cell reports: those that list it, or that carry no list at all."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def _line(value, what, problems, most=200):
    if (not isinstance(value, str) or not 1 <= len(value) <= most
            or "\n" in value or "\t" in value):
        problems.append(f"{what}: not one line of 1..{most} characters")


def problems_of(manifest):
    """Every breach found, as a list of sentences (empty: valid)."""
    bad = []
    extra = set(manifest) ^ TOP_KEYS
    if extra:
        bad.append(f"top-level keys differ from the contract's: {sorted(extra)}")
        return bad
    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 10 <= rs <= 51:
        bad.append(f"run_seconds {rs!r} outside 10..51")
    paths = manifest["paths"]
    for word in manifest["command"]:
        _line(word, f"command word {word!r}", bad)

    def named(entry, what, keys, optional=()):
        name = entry.get("name", "")
        if not NAME.match(str(name)):
            bad.append(f"{what} name {name!r} outside the allowed characters")
        wrong = (set(entry) - set(keys) - set(optional)) | (set(keys) - set(entry))
        if wrong:
            bad.append(f"{what} {name!r}: keys differ by {sorted(wrong)}")
        return name

    def unique(names, what):
        for n in {n for n in names if names.count(n) > 1}:
            bad.append(f"{what} name {n!r} appears twice")

    configs = [named(c, "configuration",
                     ("name", "source", "file", "reduced", "why"))
               for c in manifest["configs"]]
    unique(configs, "configuration")
    for c in manifest["configs"]:
        _line(c.get("source"), f"configuration {c.get('name')!r} source", bad)
        _line(c.get("why"), f"configuration {c.get('name')!r} why", bad)
        f = c.get("file", "")
        if not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            bad.append(f"configuration file {f!r} lies outside paths")
        elif not os.path.isfile(os.path.join(ROOT, f)):
            bad.append(f"configuration file {f!r} does not exist")
        else:
            bad += config_problems(c.get("name"),
                                   config_file(manifest, c.get("name")))
        for key in c.get("reduced", []):
            if not NAME.match(key):
                bad.append(f"reduced key {key!r} outside the allowed characters")

    cells = [named(w, "workload", ("name", "config", "traffic", "chips", "why"))
             for w in manifest["workloads"]]
    unique(cells, "workload")
    pairs = [(w.get("config"), w.get("traffic")) for w in manifest["workloads"]]
    for pair in {p for p in pairs if pairs.count(p) > 1}:
        bad.append(f"configuration and traffic {pair} appear twice")
    for w in manifest["workloads"]:
        _line(w.get("why"), f"workload {w.get('name')!r} why", bad)
        if w.get("config") not in configs:
            bad.append(f"workload {w.get('name')!r} names no configuration")
        if w.get("chips") not in (1, 4):
            bad.append(f"workload {w.get('name')!r}: chips is not 1 or 4")
        if not NAME.match(str(w.get("traffic", ""))):
            bad.append(f"workload {w.get('name')!r}: traffic name not allowed")
        path = os.path.join(HERE, "workloads", f"{w.get('name')}.json")
        if not os.path.isfile(path):
            bad.append(f"workload {w.get('name')!r} has no file {path}")
    four = sum(w.get("chips") == 4 for w in manifest["workloads"])
    if four > max(1, len(cells) // 2):
        bad.append(f"{four} of {len(cells)} cells ask for four chips")
    for c in configs:
        if c not in [w.get("config") for w in manifest["workloads"]]:
            bad.append(f"configuration {c!r} has no cell")

    e2e_keys = ("name", "unit", "better", "bound", "source")
    layer_keys = ("name", "unit", "better", "source", "layer", "moves")
    e2e = [named(m, "end-to-end metric", e2e_keys, ("workloads",))
           for m in manifest["end_to_end"]]
    layer = [named(m, "per-layer metric", layer_keys, ("workloads",))
             for m in manifest["per_layer"]]
    unique(e2e + layer, "metric")
    if "setup_s" not in e2e:
        bad.append("no end-to-end metric setup_s")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(str(m.get("unit", ""))):
            bad.append(f"metric {m.get('name')!r}: unit {m.get('unit')!r} not allowed")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m.get('name')!r}: better is not lower|higher")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m.get('name')!r}: source {m.get('source')!r}")
        for cell in m.get("workloads", []):
            if cell not in cells:
                bad.append(f"metric {m.get('name')!r} lists unknown cell {cell!r}")
    for m in manifest["end_to_end"]:
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end metric {m.get('name')!r}: source not taken "
                       "by the benchmark itself")
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            bad.append(f"end-to-end metric {m.get('name')!r}: bound {b!r} "
                       "outside 0.01..0.25")
    reported = {m["name"]: set(m.get("workloads", cells))
                for m in manifest["end_to_end"] if "name" in m}
    for m in manifest["per_layer"]:
        name = m.get("name")
        _line(m.get("layer"), f"per-layer metric {name!r} layer", bad)
        if "workloads" not in m:
            bad.append(f"per-layer metric {name!r} has no workloads list")
        moved = reported.get(m.get("moves"))
        if moved is None:
            bad.append(f"per-layer metric {name!r} moves {m.get('moves')!r}, "
                       "which is no end-to-end metric")
            continue
        for cell in m.get("workloads", cells):
            if cell not in moved:
                bad.append(
                    f"per_layer metric {name} is reported on workload {cell}, "
                    f"where {m['moves']}, which it should move, is not")
        if not os.path.isfile(os.path.join(HERE, "metrics", f"{name}.json")):
            bad.append(f"per-layer metric {name!r} has no file under metrics/")
    for cell in cells:
        got = [m["name"] for m in metrics_of(manifest, cell, "end_to_end")]
        if "setup_s" not in got or len(got) < 2:
            bad.append(f"cell {cell!r} reports {got}: needs setup_s and another")
        if not metrics_of(manifest, cell, "per_layer"):
            bad.append(f"cell {cell!r} reports no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad


def validate(manifest):
    bad = problems_of(manifest)
    if bad:
        raise ManifestError("; ".join(bad))
