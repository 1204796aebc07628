"""``bench/layer_metrics/cd_kernel_share.*``: the Pallas CD kernel's share
of device time, read from the ``cola.cd_kernel`` scope."""
from bench import harness
from bench.layer_metrics import _scopes


def test_cd_kernel_share_reads_the_kernel_scope(monkeypatch):
    """The kernel's scope nests in the solve's: the solve's share counts
    the kernel, and a program whose solve is a scan reads None."""
    solve = "jit(run_block)/while/body/closed_call/cola.local_solve/"
    kernel = _scopes.DeviceScopes("/device:TPU:0", 2.0, {
        solve + "cond/branch_0_fun/cola.cd_kernel/pallas_call:": 0.5,
        solve + "kdn,kd->kn/dot_general:": 0.25}, {})
    scan = _scopes.DeviceScopes("/device:TPU:0", 2.0, {
        solve + "vmap()/while/body/add:": 0.5}, {})
    read = lambda name: harness.reader("layer_metrics", name)(None)
    monkeypatch.setattr(_scopes, "scopes_of", lambda run: [kernel])
    assert read("cd_kernel_share.cert") == read("cd_kernel_share.rounds") \
        == 25.0
    assert read("solve_share.cert") == 37.5
    monkeypatch.setattr(_scopes, "scopes_of", lambda run: [scan])
    assert read("cd_kernel_share.cert") is None
    assert read("cd_kernel_share.rounds") is None
    assert read("solve_share.rounds") == 25.0
