def read(run):
    """Memories acknowledged by end_conversation over the time the writer
    took for them: the window runs to the end of its last conversation."""
    return run.memories_acked / run.window_s if run.memories_acked else None
