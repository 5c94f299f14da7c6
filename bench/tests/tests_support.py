"""Helpers shared by the benchmark's tests."""
import run


def tiny(name):
    """The cell at a size a CPU test holds: 64 envs, 4 steps a rollout."""
    cell = run.load_cell(name)
    cell.traffic = dict(cell.traffic, num_envs=64)
    cell.traffic["algo"] = dict(cell.traffic["algo"], num_steps=4)
    return cell
