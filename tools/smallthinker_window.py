"""A few SmallThinker rows decoded PAST the window, against the reference.

The benchmark's cell (``serve.reason-moe-closed256``) ends its contexts at
2,176 tokens, under the model's 4,096-token window, so its probe never
sees a window page given back.  This tool holds that on the chip, once,
outside the benchmark: ``--rows`` rows at the configuration's published
widths (``--layers`` of its layers: one period by default) are decoded,
teacher-forced through ``engine.put``, from a short prompt to ``window +
--past`` tokens, and the served logits are compared with the float32
reference (``benchmark/reference_smallthinker.py``) BEFORE the first
window page is evicted (contexts under the window) and AFTER (contexts
past ``window + page``).  Two controls read the same served rows against
a reference whose window is a page short and a page long: equal before,
at least twice the sound reading after.

    chiprun -- python3 tools/smallthinker_window.py --seed 7
    python3 tools/smallthinker_window.py --rehearse     # CPU, debug widths

The last line of stdout is one JSON object (``ok``, the medians, the pages
released); it is also written to ``chiprun_out/smallthinker_window.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--past", type=int, default=192,
                    help="tokens decoded past the window")
    ap.add_argument("--limit", type=float, default=None,
                    help="limit on a span's median relative rms "
                         "(default: the configuration's probe limit)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, debug widths, a window of 128, float32")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmark.builders import serve_smallthinker as builder
    from benchmark.builders.serve_pangu_moe import rel_rms
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-serve-8l.json")) as f:
        config = json.load(f)
    config["num_hidden_layers"] = args.layers
    if args.rehearse:
        config["rehearse"].update(sliding_window_size=128,
                                  num_hidden_layers=args.layers)
    c = builder.sized(config, args.rehearse)
    window, page = c["sliding_window_size"], 16 if args.rehearse else 64
    length = window + args.past
    slots = -(-length // page) + 2
    config["engine"].update(
        page_size=page, num_pages=args.rows * slots,
        window_num_pages=args.rows * slots, max_sequences=args.rows,
        token_budget=max(256, args.rows * args.prompt), max_seq_len=2 * window,
        serving={})
    limit = args.limit if args.limit is not None else (
        3e-4 if args.rehearse else config["probe"]["logit_rel_rms"])

    cfg, params = builder.make_model(config, args.seed, args.rehearse)
    rng = np.random.default_rng([args.seed % 2 ** 63, 47])
    seqs = [rng.integers(0, cfg.vocab_size, length).astype(np.int32)
            for _ in range(args.rows)]
    # the compared positions: a span before ANY of the three windows
    # closes, a span after the first eviction under each of them (a
    # context past window + page has given a page back)
    keep = np.r_[window - 3 * page:window - page, window + page:length]

    def reference(**controls):
        return [builder.reference_side(params, cfg, [s], **controls)[0][0][
            keep] for s in seqs]

    want = {"sound": reference(),
            "window_less_a_page": reference(window=window - page),
            "window_plus_a_page": reference(window=window + page)}
    engine = builder.make_engine(cfg, params, config["engine"],
                                 args.rehearse)
    uids = list(range(args.rows))
    engine.put(uids, [s[:args.prompt] for s in seqs])
    at = {int(p): n for n, p in enumerate(keep)}
    err = {name: np.zeros((args.rows, len(keep))) for name in want}
    for t in range(args.prompt, length):
        got = np.asarray(engine.put(uids, [s[t:t + 1] for s in seqs]))
        if t in at:
            for name, rows in want.items():
                err[name][:, at[t]] = rel_rms(
                    got, np.stack([r[at[t]] for r in rows]))
    state = engine.state_manager
    state.check_invariants()
    before = keep < window
    out = {"rows": args.rows, "layers": args.layers, "window": window,
           "tokens": length, "compared": int(len(keep)) * args.rows,
           "limit": limit,
           "window_pages_released": int(state.window_pages_released),
           "device": jax.devices()[0].device_kind}
    for name, e in err.items():
        out[name] = {"before": round(float(np.median(e[:, before])), 5),
                     "after": round(float(np.median(e[:, ~before])), 5),
                     "after_max": round(float(e[:, ~before].max()), 5)}
    sound = out["sound"]
    # sound: under the probe's limit on both sides of the first eviction
    # and no worse after it; a window a page off reads the same before and
    # at least twice the sound reading after (a page is 64 of 4,096
    # attended tokens in three layers of four: the fault doubles a row's
    # error, it does not reach the probe's limit)
    out["ok"] = bool(
        sound["before"] <= limit and sound["after"] <= limit
        and sound["after"] <= 1.25 * sound["before"] + 1e-6
        and state.window_pages_released >= args.rows * (args.past // page - 1)
        and all(out[c]["after"] > max(2 * sound["after"], 1e-4)
                and abs(out[c]["before"] - sound["before"]) < 0.2 * limit
                for c in ("window_less_a_page", "window_plus_a_page")))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "smallthinker_window.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
