"""Prompt tokens prefilled plus tokens generated by the window's steps,
over the window's seconds."""
import stats


def read(rec):
    return stats.window_tokens(rec) / rec["window"]["seconds"]
