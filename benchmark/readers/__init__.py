"""One module per kind of reader: ``read(args, obs) -> number | None``.
``obs`` is what a traced run observed (see ``run.py`` ``observations``);
a reader that finds nothing to read returns None and the metric is left
out of the line. A new kind of reader is a new file here."""
