"""Shared test utilities.

``capture_logs`` exists because ``paddle_tpu.base.log.get_logger`` sets
``propagate=False`` on the framework logger — pytest's ``caplog`` fixture
hooks the root logger, so it silently captures NOTHING from the
framework. Every test that asserts on framework log output must attach a
handler directly; this context manager is that idiom in one place."""
from __future__ import annotations

import contextlib
import io
import logging


@contextlib.contextmanager
def capture_logs(level: int = logging.INFO, logger: logging.Logger = None):
    """Capture framework log output into a ``StringIO``.

    Attaches a ``StreamHandler`` directly to the paddle_tpu logger (or
    the one given), temporarily lowers its level to ``level``, and
    restores both on exit::

        with capture_logs() as buf:
            thing_that_logs()
        assert "expected fragment" in buf.getvalue()
    """
    if logger is None:
        from paddle_tpu.base.log import get_logger

        logger = get_logger()
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    prev_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(level)
    try:
        yield buf
    finally:
        logger.removeHandler(handler)
        logger.setLevel(prev_level)
