"""Serving launcher: batched requests through the ragged token-budget
engine (``--engine reference`` runs the seed lock-step engine instead).
``--scheduler`` swaps the admission/packing policy (fifo | prefix-aware |
slo); with ``slo``, ``--interactive-every N`` marks every Nth request
priority 1 so the policy has two classes to separate.

The model is built at its published width unless ``--smoke`` asks for the
small preset (what CPU runs use):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke --requests 12
"""
import argparse
import os

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, skip_reason
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.reference import ReferenceEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's small smoke preset instead of its "
                         "published width")
    ap.add_argument("--engine", choices=("ragged", "reference"),
                    default="ragged")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-pages", type=int, default=None,
                    help="physical page-pool budget (default: full)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--token-budget", type=int, default=128,
                    help="tokens per ragged tick (prefill + decode blend)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples with --top-k/--seed")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seed base")
    ap.add_argument("--flash-decode", action="store_true",
                    help="route global-layer decode through the Pallas "
                         "paged kernel")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the refcounted prefix cache / COW pages "
                         "(sharing is auto-disabled for hybrid models)")
    ap.add_argument("--kv-dtype", choices=("float32", "bfloat16", "int8"),
                    default=None,
                    help="paged KV pool storage dtype (default: activation "
                         "dtype); int8 quantizes on write with per-entry-"
                         "per-head scales and holds 2-4x the pages in the "
                         "same pool bytes")
    ap.add_argument("--scheduler", choices=("fifo", "prefix-aware", "slo"),
                    default="fifo",
                    help="admission/packing policy (fifo reproduces the "
                         "pre-policy engine exactly)")
    ap.add_argument("--interactive-every", type=int, default=0, metavar="N",
                    help="mark every Nth request priority 1 (the "
                         "interactive class the slo scheduler serves first)")
    args = ap.parse_args(argv)

    if skip_reason(args.arch, "decode_32k"):
        raise SystemExit(f"{args.arch}: {skip_reason(args.arch, 'decode_32k')}")
    # before the backend starts: libtpu otherwise logs to a fixed directory
    # outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    cache_len = max(128, args.prompt_len + args.max_tokens)
    if args.engine == "reference":
        engine = ReferenceEngine(params, cfg, batch_size=args.batch_size,
                                 cache_len=cache_len)
    else:
        engine = ServeEngine(params, cfg, batch_size=args.batch_size,
                             cache_len=cache_len, page_size=args.page_size,
                             max_pages=args.max_pages,
                             prefill_chunk=args.prefill_chunk,
                             token_budget=args.token_budget,
                             flash_decode=args.flash_decode,
                             prefix_cache=not args.no_prefix_cache,
                             kv_dtype=args.kv_dtype,
                             scheduler=args.scheduler)
    rng = np.random.RandomState(0)
    sample_kw = {}
    if args.engine != "reference" and args.temperature > 0:
        sample_kw = dict(temperature=args.temperature, top_k=args.top_k)
    def _priority(i):
        if args.engine == "reference" or not args.interactive_every:
            return {}
        return {"priority": int((i + 1) % args.interactive_every == 0)}

    uids = [engine.submit(rng.randint(0, cfg.vocab_size, args.prompt_len),
                          max_tokens=args.max_tokens,
                          **(dict(sample_kw, seed=(args.seed or 0) + i)
                             if sample_kw else {}),
                          **_priority(i))
            for i in range(args.requests)]
    results = engine.run()
    for uid in uids:
        print(f"req {uid:3d}: {results[uid]}")
    if args.engine != "reference":
        print(f"stats: {engine.stats}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
