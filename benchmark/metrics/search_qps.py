def read(run):
    """All retrievals completed inside the window over the window's length."""
    if not run.completed_in_window:
        return None
    return run.completed_in_window / run.seconds
