"""Programs compiled (or read from the compile cache) inside the window: the
program's cumulative `compile.n` on the window's last record less that on the
last record before the window. Nothing compiles in a sound window."""


def read(run):
    recs = [r for r in run["window_records"] if "compile" in r]
    if not recs:
        return None
    before = [r["compile"]["n"] for r in run["records"]
              if "compile" in r and r["step"] < recs[0]["step"]]
    base = before[-1] if before else recs[0]["compile"]["n"]
    return recs[-1]["compile"]["n"] - base
