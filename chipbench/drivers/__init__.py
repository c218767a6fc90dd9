"""One module per traffic kind, found by the ``kind`` a traffic file gives."""
