"""Top-K words — WordCount + the fused order_by+take top-k.

The classic query (count words, show the 10 most frequent) compiles to
ONE fused stage: partial count → hash ``all_to_all`` → final count →
local top-k → one ``all_gather`` of the P heads — the full range
exchange a naive sort-then-take would pay disappears (plan rewrite,
``plan/lower.py _rewrite_topk``; reference SimpleRewriter.cs).

Run:
    JAX_PLATFORMS=cpu python samples/top_words.py [textfile]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The CPU-mesh demo path: pin the virtual mesh before the first backend
# query.  Without JAX_PLATFORMS=cpu the sample runs on jax's default
# devices (the chip).
if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    from dryad_tpu.parallel.mesh import force_cpu_backend

    force_cpu_backend(8)

import numpy as np

from dryad_tpu import DryadContext
from dryad_tpu.tools.explain import explain


def main() -> None:
    ctx = DryadContext()
    if len(sys.argv) > 1:
        q = ctx.from_text(sys.argv[1])
    else:
        rng = np.random.default_rng(0)
        vocab = np.array(
            "the quick brown fox jumps over a lazy dog and cat".split(),
            object,
        )
        words = vocab[
            rng.choice(len(vocab), 50_000, p=np.linspace(1, 2, len(vocab))
                       / np.linspace(1, 2, len(vocab)).sum())
        ]
        q = ctx.from_arrays({"word": words})

    top = (
        q.group_by("word", {"count": ("count", None)})
        .order_by([("count", True)])
        .take(10)
    )
    print(explain(top))
    print()
    out = top.collect()
    for w, c in zip(out["word"], out["count"]):
        print(f"{c:>8}  {w}")


if __name__ == "__main__":
    main()
