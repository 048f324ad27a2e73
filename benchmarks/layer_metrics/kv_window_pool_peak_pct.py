"""Peak of the window group's live pages in the window, of its pages."""


def read(run):
    if not run.get("window_pages"):
        return None
    return 100.0 * run["window_pages_peak"] / run["window_pages"]
