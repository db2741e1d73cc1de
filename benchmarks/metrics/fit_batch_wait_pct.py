"""Share of the window that ``fit()`` spent blocked on the batch iterator:
the sum of ``t_batch_wait_s`` over the window's own metrics records. The
train kind draws its batches from the seed before ``fit()`` starts
(``batches_drawn_ahead`` of the cell's file), so this reads the trainer's
own hand-over of a ready batch (its prefetch thread, the copy to the device),
not the cost of a data loader: a cell that loads data brings its own feed."""

UNIT = "%"


def read(run):
    rows = [m for _, _, m in run["records"] if "t_batch_wait_s" in m]
    if not rows:
        return None
    return 100.0 * sum(m["t_batch_wait_s"] for m in rows) / run["window"]["seconds"]
