"""One driver per kind of work a traffic mix names (``"kind"``)."""
