"""Images completed in the window over the window's length.  The window
opens when the clients start and closes at the first batch completion at
or after ``--seconds``, so it holds whole batches and every second."""


def read(run):
    if run.close_s <= 0:
        return None
    return len(run.in_window()) / run.close_s
