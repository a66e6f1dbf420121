"""What the readers share: views of one run's observations (`obs`, the
dict a cell's driver returns) restricted to the measured window."""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple


def in_window(obs: Dict[str, Any], t: float) -> bool:
    return obs["t_w"] <= t < obs["t_end"]


def due_time(obs: Dict[str, Any], req) -> float:
    """When the request was due to be sent: on the schedule in an open
    loop; when its caller became free (= sent) in a closed one."""
    if req.due is not None:
        return obs["t_load"] + req.due
    return req.sent


def gaps(obs: Dict[str, Any]) -> List[Tuple[float, float]]:
    """(time the gap ended, gap in ms) for every pair of consecutive
    output tokens of one request, the later token inside the window.
    The first token of a request ends no gap."""
    out = []
    for r in obs["requests"]:
        ts = r.token_times
        out.extend((b, (b - a) * 1e3) for a, b in zip(ts, ts[1:])
                   if in_window(obs, b))
    return out


def first_tokens(obs: Dict[str, Any]) -> Iterator[Tuple[Any, float]]:
    """(request, time of its first token) for first tokens that arrived
    inside the window."""
    for r in obs["requests"]:
        if r.token_times and in_window(obs, r.token_times[0]):
            yield r, r.token_times[0]


def tokens_between(obs: Dict[str, Any], t0: float, t1: float
                   ) -> Iterator[Tuple[Any, int, float]]:
    """(request, index of the token in its output, time) for every token
    delivered in [t0, t1)."""
    for r in obs["requests"]:
        for i, t in enumerate(r.token_times):
            if t0 <= t < t1:
                yield r, i, t


def note(obs: Dict[str, Any], key: str, value: Any) -> None:
    """A reader's by-product worth printing on an earlier line."""
    obs.setdefault("notes", {})[key] = value
