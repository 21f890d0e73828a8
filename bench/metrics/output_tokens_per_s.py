"""Output tokens that reached clients during the window, over the
window."""


def read(run):
    n = run.output_tokens_in_window()
    return n / run.window_s if n else None
