"""Rootdir conftest (pytest only honors ``pytest_addoption`` from
here).

pytest.ini pins ``--numprocesses=4 --dist loadfile`` (xdist). When
xdist is disabled — the tier-1 command passes ``-p no:xdist`` — those
pinned addopts would die at argument parsing before a single test runs.
Re-register the flags as inert in that case, so the run degrades to one
process instead of erroring out. (Lowercase short options like ``-n``
are reserved by pytest, which is why the ini uses the long spelling.)
"""


def pytest_addoption(parser, pluginmanager):
    if pluginmanager.hasplugin("xdist"):
        return  # real xdist is loaded and owns these flags
    parser.addoption("--numprocesses", dest="_no_xdist_n", default=None)
    parser.addoption("--dist", dest="_no_xdist_dist", default=None)
