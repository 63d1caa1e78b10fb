"""Each benchmark test ends with the program's recording closed, also
when its run raised before the readers read (`bench.lib.program`)."""
import pytest

from bench.lib import program


@pytest.fixture(autouse=True)
def _program_recording_closed():
    yield
    program.close()
