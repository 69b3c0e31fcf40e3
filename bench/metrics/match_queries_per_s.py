"""Titles whose request was answered inside the window, over the
window's length."""


def read(rec):
    if rec.get("kind") != "serve":
        return None
    return rec["titles_in_window"] / rec["window_s"]
