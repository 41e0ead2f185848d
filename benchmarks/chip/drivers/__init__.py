"""Traffic drivers, one module per kind of request, found by the ``driver``
name a traffic file gives (see ``traffic.py``)."""
