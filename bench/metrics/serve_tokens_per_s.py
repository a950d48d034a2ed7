"""Output tokens the engine emitted inside the window, over the window."""


def read(run):
    return run.extra["emitted_tokens"] / run.window_s
