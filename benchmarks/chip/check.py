"""The comparison that decides a run's ``correct``.

After the window closes, a seeded sample of the batches the window
emitted (two that the quality probe scored, two that it did not) is
recomputed by the plain reference (``reference.py`` and the module the
configuration names) from the same documents, batch keys and weights,
and every stage is compared:

- ``extract_diff``  documents whose cheap-parser pages differ (exact)
- ``feature_gap``   largest CLS-I feature difference
- ``token_diff``    rows whose first-page tokens or mask differ (exact)
- ``override_diff`` documents whose CLS-I override differs (exact)
- ``pred_acc_gap``  largest predicted-accuracy difference of the route
                    step (llm router)
- ``select_diff``   batches whose selected set differs from the routing
                    rule: on the route step's own improvement for the
                    llm router, on the reference's CLS-I/II decision for
                    the ft router (exact)
- ``record_diff``   emitted records that differ in parser or pages,
                    given the batch's selected set (exact)
- ``probe_diff``    window batches probed against the sampling rule, and
                    scored groups of another size (exact)
- ``probe_gap``     largest per-parser mean BLEU difference of a probed
                    batch

A configuration with a parser model adds the numbers its module's
``readings`` gives for each sampled batch (``harness`` lists the
hooks). Each number has its limit in the configuration file; the run is
correct when every number is at or under its limit.
"""
from __future__ import annotations

import numpy as np

import reference as R

EXACT = ("extract_diff", "token_diff", "override_diff", "select_diff",
         "record_diff", "probe_diff")


def program_sample(row: dict, variant: str) -> dict:
    """What the timed path produced for one batch, as host arrays."""
    prep, out = row["prep"], row.get("route_out")
    s = {"key": prep.batch_key, "docs": prep.docs,
         "extracted": prep.extracted,
         "fast": np.asarray(prep.fast, np.float64),
         "selected": np.asarray(row["plan"].expensive_idx, np.int64),
         "records": [(r.parser, r.pages) for r in row["records"]],
         "quality": row.get("quality")}
    if "parser" in row:
        s["parser"] = row["parser"]
    if variant == "llm":
        s.update(tokens=np.asarray(prep.route_host["tokens"]),
                 mask=np.asarray(prep.route_host["mask"]),
                 pred_acc=np.asarray(out["pred_acc"], np.float64),
                 improvement=np.asarray(out["improvement"]))
    return s


class Reference:
    """The plain reference of one cell: its configuration, corpus,
    routing stages and weights, and its parser model
    (``harness.ParserModel``) where it has one, fixed for the run."""

    def __init__(self, config: dict, traffic: dict, engine_seed: int,
                 probe_seed: int, stages: dict, weights=None, encoder=None,
                 parser=None):
        self.cfg, self.traffic = config, traffic
        self.corpus = traffic["corpus"]
        self.engine_seed, self.probe_seed = engine_seed, probe_seed
        self.stages, self.weights, self.encoder = stages, weights, encoder
        self.parser = parser
        #: the numbers summed over batches; the others fold by the max
        self.exact = EXACT + (tuple(parser.hook("EXACT") or ())
                              if parser is not None else ())

    def batch(self, docs, key: int, precision: str = "exact",
              selected: np.ndarray | None = None) -> dict:
        """The reference's outputs for batch ``key``. Records follow
        ``selected`` when given (the program's verified selection), else
        the reference's own."""
        cfg = self.cfg
        rng = R.batch_rng(self.engine_seed, key)
        extracted = R.channel(docs, cfg["cheap"], self.corpus, rng)
        after_cheap = rng.get_state()
        fast = np.stack([R.fast_features(p, self.corpus, precision)
                         for p in extracted])
        valid = R.logistic(fast, *self.stages["cls1"]) \
            >= cfg["valid_threshold"]
        s = {"key": key, "docs": docs, "extracted": extracted,
             "fast": fast, "valid": valid}
        if cfg["variant"] == "llm":
            toks, mask = zip(*(R.first_page(p, cfg["encoder"]["max_len"])
                               for p in extracted))
            s["tokens"], s["mask"] = np.stack(toks), np.stack(mask)
            pred = self.encoder.predict(self.weights, cfg["encoder"],
                                        s["tokens"], s["mask"], precision)
            s["pred_acc"] = pred.astype(np.float64)
            imp = pred[:, cfg["expensive_index"]] - pred[:, cfg["cheap_index"]]
        else:
            meta = np.stack([R.metadata(d) for d in docs])
            imp = R.logistic(meta, *self.stages["cls2"]) \
                - cfg["improve_threshold"]
        s["improvement"] = np.where(valid, imp, R.CLS1_OVERRIDE).astype(
            imp.dtype)
        s["selected"] = R.select(s["improvement"], cfg["alpha"])
        sel = s["selected"] if selected is None else selected
        rng.set_state(after_cheap)
        pages = R.channel([docs[i] for i in sel], cfg["expensive"],
                          self.corpus, rng)
        by_sel = {int(i): j for j, i in enumerate(sel)}
        s["records"] = [(cfg["expensive"], pages[by_sel[i]]) if i in by_sel
                        else (cfg["cheap"], extracted[i])
                        for i in range(len(docs))]
        s["quality"] = (self.quality(docs, s["records"], precision)
                        if self.probed(key) else None)
        return s

    def probed(self, key: int) -> bool:
        return R.probed(self.probe_seed, key, self.traffic["probe_rate"])

    def quality(self, docs, records, precision: str = "exact") -> dict:
        scores: dict[str, list[float]] = {}
        for d, (parser, pages) in zip(docs, records):
            hyp = (np.concatenate(pages) if sum(map(len, pages))
                   else np.zeros(0, np.int32))
            scores.setdefault(parser, []).append(R.bleu(
                d.full_text(), hyp, self.traffic["probe_max_len"],
                precision))
        return {p: (float(np.mean(v)), len(v)) for p, v in scores.items()}


def compare(prog: dict, ref: Reference) -> dict:
    """Readings of one batch: ``prog`` (program or control outputs)
    against the exact reference."""
    cfg = ref.cfg
    llm = cfg["variant"] == "llm"
    want = ref.batch(prog["docs"], prog["key"], "exact",
                     selected=prog["selected"])
    r = {"extract_diff": sum(not _same_pages(a, b) for a, b in
                             zip(prog["extracted"], want["extracted"])),
         "feature_gap": float(np.max(np.abs(prog["fast"] - want["fast"])))}
    if llm:
        r["token_diff"] = int(np.sum(
            np.any(prog["tokens"] != want["tokens"], axis=1)
            | np.any(prog["mask"] != want["mask"], axis=1)))
        r["override_diff"] = int(np.sum(
            (prog["improvement"] == R.CLS1_OVERRIDE) != ~want["valid"]))
        r["pred_acc_gap"] = float(np.max(np.abs(prog["pred_acc"]
                                                - want["pred_acc"])))
        rule = R.select(prog["improvement"], cfg["alpha"])
    else:
        rule = want["selected"]
    r["select_diff"] = int(not np.array_equal(rule, prog["selected"]))
    r["record_diff"] = sum(
        pa != pb or not _same_pages(a, b)
        for (pa, a), (pb, b) in zip(prog["records"], want["records"]))
    r["probe_diff"] = int((prog["quality"] is None)
                          != (want["quality"] is None))
    if prog["quality"] is not None and want["quality"] is not None:
        got, exp = prog["quality"], want["quality"]
        r["probe_diff"] += int({p: n for p, (_, n) in got.items()}
                               != {p: n for p, (_, n) in exp.items()})
        r["probe_gap"] = max(abs(got[p][0] - exp[p][0])
                             for p in got.keys() & exp.keys())
    readings = ref.parser and ref.parser.hook("readings")
    if readings is not None:
        r.update(readings(prog, ref.parser.weights, ref.parser.widths))
    return r


def fold(readings: list[dict], window_probe_diff: int,
         exact: tuple = EXACT) -> dict:
    """One run's numbers: the ``exact`` counts summed, gaps at their
    largest."""
    out: dict = {}
    for r in readings:
        for name, v in r.items():
            if name in exact:
                out[name] = out.get(name, 0) + v
            else:
                out[name] = max(out.get(name, 0.0), v)
    out["probe_diff"] = out.get("probe_diff", 0) + window_probe_diff
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: [number, limit]}) over every limit the
    configuration states; a number the run could not read fails."""
    checks = {name: [numbers.get(name), lim] for name, lim in limits.items()}
    ok = all(v is not None and v <= lim for v, lim in checks.values())
    return ok, checks


def _same_pages(a, b) -> bool:
    return len(a) == len(b) and all(np.array_equal(x, y)
                                    for x, y in zip(a, b))


def control_numbers(ref: Reference, batches, per_stratum: int = 2,
                    scan: int = 64) -> dict:
    """The control's numbers: the reference one precision lower, put in
    the program's place, over the first ``per_stratum`` probed and
    unprobed batches among the first ``scan`` of the cell's own stream
    ``batches``."""
    want = {True: per_stratum, False: per_stratum}
    readings = []
    for (key, docs), _ in zip(batches, range(scan)):
        p = ref.probed(key)
        if want[p]:
            want[p] -= 1
            readings.append(compare(ref.batch(docs, key, "control"), ref))
        if not any(want.values()):
            break
    return fold(readings, 0, ref.exact)
