"""One module per call a traffic mix drives. A mix names its driver in
``"driver"``; each driver has ``setup``, ``window`` and ``check``."""
