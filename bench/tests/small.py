"""Small cells for CPU tests: each configuration at its program's
``reduced()`` size (its family's ``small``, float32 compute) and traffic
shrunk to fit a test."""
import json

from repro.configs import get_reduced

from bench import spec


def shrink(cfg: dict, family):
    """(``cfg`` at the reduced size, the program's reduced ModelConfig)."""
    m = get_reduced(cfg["program"])
    cfg = dict(family.small(cfg, m), compute_dtype="float32")
    # At this width, weights of std 0.02 leave every position's top logit on
    # its own token whatever the context; at 0.3 the context decides it (as
    # it does at the published width with 0.02), so a broken cache shows.
    cfg["init"] = dict(cfg["init"], std=0.3)
    return cfg, m


def config(name: str):
    """(configuration dict, the program's ModelConfig) at the reduced size."""
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    return shrink(cfg, spec.family(cfg["family"]))


def cell(workload: str, root=spec.ROOT):
    c = spec.load_cell(workload, root)
    c.config, model = shrink(c.config, c.family)
    t = c.traffic
    if t["kind"] == "train":
        t.update(batch=2, seq=32, stride_steps=2, check_rows=1)
    else:
        t["serve"] = {"page_size": 8, "prefill_chunk": 16, "max_seq": 128,
                      "max_slots": 4}
        t["check_requests"] = 3
        if t["kind"] == "serve_open":
            t.update(rate_rps=4.0, warmup_s=0.5, drain_limit_s=30,
                     prompt={"dist": "lognormal", "median": 20, "sigma": 1.0,
                             "min": 4, "max": 60},
                     output={"dist": "lognormal", "median": 6, "sigma": 0.8,
                             "min": 2, "max": 12})
        else:
            t.update(backlog=4,
                     prompt={"dist": "uniform", "min": 40, "max": 90},
                     output={"dist": "uniform", "min": 4, "max": 12})
    return c, model
