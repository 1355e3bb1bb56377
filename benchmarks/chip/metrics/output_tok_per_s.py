"""Output tokens per second (host clock): every token delivered to any
request in the window, over the window's seconds."""


def read(run):
    return sum(1 for _ in run.window_tokens()) / run.window_s
