"""Tokens per fused decode block over the window: the engines'
``decode_tokens`` over their ``steps``. Catches both empty slots and
blocks cut short."""


def read(run):
    c0, c1 = run.out.c0, run.out.c1
    tok = sum(c1[t]["decode_tokens"] - c0[t]["decode_tokens"]
              for t in run.models)
    steps = sum(c1[t]["steps"] - c0[t]["steps"] for t in run.models)
    return tok / steps if steps > 0 else None
