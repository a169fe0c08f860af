"""Output tokens all engines streamed inside the window, over the window:
the engines' ``decode_tokens`` read at the window's two ends."""


def read(run):
    c0, c1 = run.out.c0, run.out.c1
    n = sum(c1[t]["decode_tokens"] - c0[t]["decode_tokens"]
            for t in run.models)
    return n / (c1["t"] - c0["t"])
