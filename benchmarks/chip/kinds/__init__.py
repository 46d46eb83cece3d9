"""Job drivers, one per traffic ``kind``: ``run(ctx)`` builds the job,
marks the end of set-up, drives the measured window and checks the
result against the reference."""
