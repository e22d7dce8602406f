"""The benchmark's traffic generator: a synthetic central-European PV fleet
and the forecaster's training windows.

A copy of ``repro.data.solar`` (``generate_fleet``) and
``repro.data.windows`` (``make_windows``, ``split_windows``,
``batch_iter``), kept here so that a change to the program's data code
cannot move the yardstick.  The program receives only what these functions
return: client static features and window arrays.

One departure from the program's copy: a site's noise stream is seeded
from ``zlib.crc32`` of its id, not from Python's ``hash()``, which
``PYTHONHASHSEED`` randomises per process.  With ``hash()`` two processes
given the same seed generate different production series.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass

import numpy as np

FEATURES = (
    "solar_rad", "ghi", "snow_depth", "precip", "clouds",
    "minute_of_day_sin", "minute_of_day_cos", "day_of_year_sin",
    "day_of_year_cos",
)
STEPS_PER_DAY = 96                       # 15-minute intervals
HISTORY_STEPS = 7 * STEPS_PER_DAY        # 672
HORIZON_STEPS = STEPS_PER_DAY            # 96

# Table I normalization ranges (regional maxima, central Europe)
RANGES = {
    "solar_rad": 956.2,
    "ghi": 956.21,
    "snow_depth": 1178.6,
    "precip": 14.78,
    "clouds": 100.0,
}
CENTERS = [(48.21, 16.37), (48.14, 11.58), (47.38, 8.54),
           (50.08, 14.44), (47.07, 15.44)]
AZIMUTHS = [180.0, 110.0, 250.0]


@dataclass(frozen=True)
class SiteSpec:
    site_id: str
    lat: float
    lon: float
    azimuth: float          # panel azimuth, deg (180 = due south)
    tilt: float             # deg from horizontal
    kwp: float              # rated capacity
    region: int             # weather-region index (correlated clouds)
    noise: float = 0.02

    @property
    def static_features(self) -> dict:
        return {"loc": np.array([self.lat, self.lon]),
                "ori": np.array([self.azimuth])}


def _solar_geometry(day_of_year, minute_of_day, lat_deg):
    decl = np.radians(23.45) * np.sin(2 * np.pi * (284 + day_of_year) / 365.0)
    hour_angle = np.radians((minute_of_day / 4.0) - 180.0)
    lat = np.radians(lat_deg)
    sin_el = (np.sin(lat) * np.sin(decl)
              + np.cos(lat) * np.cos(decl) * np.cos(hour_angle))
    el = np.arcsin(np.clip(sin_el, -1, 1))
    cos_az = ((np.sin(decl) - np.sin(el) * np.sin(lat))
              / np.maximum(np.cos(el) * np.cos(lat), 1e-6))
    az = np.arccos(np.clip(cos_az, -1, 1))
    az = np.where(hour_angle > 0, 2 * np.pi - az, az)
    return el, az


def _clear_sky_ghi(elevation):
    sin_el = np.maximum(np.sin(elevation), 0.0)
    am = 1.0 / np.maximum(sin_el, 0.05)
    return 1100.0 * sin_el * (0.7 ** (am ** 0.678))


def _panel_factor(elevation, sun_az, panel_az_deg, tilt_deg):
    tilt = np.radians(tilt_deg)
    paz = np.radians(panel_az_deg)
    cos_inc = (np.sin(elevation) * np.cos(tilt)
               + np.cos(elevation) * np.sin(tilt) * np.cos(sun_az - paz))
    return np.maximum(cos_inc, 0.0)


class SolarDataGenerator:
    """Generates (features, production) for a fleet of sites over N days."""

    def __init__(self, n_days: int, seed: int, start_day: int = 0):
        self.n_days = n_days
        self.seed = seed
        self.start_day = start_day
        self._region_weather: dict[int, dict] = {}

    def _weather(self, region: int) -> dict:
        if region in self._region_weather:
            return self._region_weather[region]
        rng = np.random.default_rng(self.seed * 7919 + region)
        T = self.n_days * STEPS_PER_DAY
        day = (self.start_day + np.arange(T) / STEPS_PER_DAY) % 365.0
        seasonal = 0.55 - 0.25 * np.cos(2 * np.pi * (day - 15) / 365.0)
        daily = np.zeros(self.n_days)
        daily[0] = rng.uniform(0, 1)
        for i in range(1, self.n_days):
            daily[i] = np.clip(0.7 * daily[i - 1] + 0.3 * rng.uniform(0, 1)
                               + rng.normal(0, 0.1), 0, 1)
        clouds = np.clip(
            seasonal * np.repeat(daily, STEPS_PER_DAY)
            + 0.15 * rng.normal(0, 1, T).cumsum()
            / np.sqrt(np.arange(1, T + 1)), 0, 1) * 100.0
        precip = np.where((clouds > 70) & (rng.random(T) < 0.3),
                          rng.gamma(1.5, 1.2, T), 0.0)
        precip = np.clip(precip, 0, RANGES["precip"])
        winter = np.maximum(np.cos(2 * np.pi * day / 365.0), 0.0)
        snow = np.zeros(T)
        s = 0.0
        for i in range(T):
            s += 4.0 * precip[i] * winter[i]
            s *= (1.0 - 0.002 * (1.05 - winter[i]))
            snow[i] = s
        snow = np.clip(snow, 0, RANGES["snow_depth"])
        w = {"clouds": clouds, "precip": precip, "snow": snow, "day": day}
        self._region_weather[region] = w
        return w

    def generate_site(self, site: SiteSpec) -> dict:
        rng = np.random.default_rng(
            self.seed * 104729 + zlib.crc32(site.site_id.encode()))
        T = self.n_days * STEPS_PER_DAY
        w = self._weather(site.region)
        day = w["day"]
        minute = (np.arange(T) % STEPS_PER_DAY) * (1440 // STEPS_PER_DAY)
        el, az = _solar_geometry(day, minute, site.lat)
        ghi_clear = _clear_sky_ghi(el)
        cloud_att = 1.0 - 0.75 * (w["clouds"] / 100.0) ** 2
        solar_rad = ghi_clear * cloud_att
        panel = _panel_factor(el, az, site.azimuth, site.tilt)
        snow_block = np.exp(-w["snow"] / 80.0)
        rain_loss = 1.0 - 0.05 * (w["precip"] > 0.5)
        prod_norm = (panel * cloud_att * snow_block * rain_loss
                     * (ghi_clear / 1000.0))
        prod_norm = np.clip(prod_norm * (1 + rng.normal(0, site.noise, T)),
                            0, 1.2)

        def hourly_forecast(x, err):
            xh = x.reshape(-1, 4).mean(1)
            xh = xh * (1 + rng.normal(0, err, len(xh)))
            return np.repeat(xh, 4)

        feats = {
            "solar_rad": np.clip(hourly_forecast(solar_rad, 0.08), 0,
                                 RANGES["solar_rad"]),
            "ghi": np.clip(hourly_forecast(ghi_clear, 0.02), 0, RANGES["ghi"]),
            "snow_depth": np.clip(hourly_forecast(w["snow"], 0.05), 0,
                                  RANGES["snow_depth"]),
            "precip": np.clip(hourly_forecast(w["precip"], 0.2), 0,
                              RANGES["precip"]),
            "clouds": np.clip(hourly_forecast(w["clouds"], 0.12), 0,
                              RANGES["clouds"]),
        }
        cols = []
        for name in FEATURES:
            if name == "minute_of_day_sin":
                cols.append(np.sin(2 * np.pi * minute / 1440.0))
            elif name == "minute_of_day_cos":
                cols.append(np.cos(2 * np.pi * minute / 1440.0))
            elif name == "day_of_year_sin":
                cols.append(np.sin(2 * np.pi * day / 365.0))
            elif name == "day_of_year_cos":
                cols.append(np.cos(2 * np.pi * day / 365.0))
            else:
                cols.append(feats[name] / RANGES[name])
        X = np.stack(cols, axis=1).astype(np.float32)
        return {"features": X, "production_norm": prod_norm.astype(np.float32),
                "minute": minute, "day": day}


def fleet_sites(n_sites: int, seed: int, n_regions: int = 3,
                prefix: str = "site") -> list[SiteSpec]:
    """Sites cluster geographically into regions (Vienna / Munich /
    Zurich-ish) and by panel azimuth (S / E / W)."""
    rng = np.random.default_rng(seed)
    sites = []
    for i in range(n_sites):
        region = i % n_regions
        lat0, lon0 = CENTERS[region]
        sites.append(SiteSpec(
            site_id=f"{prefix}{i:04d}",
            lat=lat0 + rng.normal(0, 0.25),
            lon=lon0 + rng.normal(0, 0.35),
            azimuth=(AZIMUTHS[(i // n_regions) % 3] + rng.normal(0, 8.0)) % 360,
            tilt=rng.uniform(20, 40),
            kwp=float(rng.choice([5.0, 8.0, 10.0, 15.0, 30.0, 100.0])),
            region=region,
            noise=rng.uniform(0.01, 0.04)))
    return sites


def generate_fleet(n_sites: int, n_days: int, seed: int, n_regions: int = 3,
                   start_day: int = 90, prefix: str = "site",
                   layout_seed: int | None = None
                   ) -> list[tuple[SiteSpec, dict]]:
    """``n_sites`` sites with ``n_days`` of 15-minute data each, from
    ``start_day`` of the year (spring onward: the strongest signal).  The
    sites are placed from ``layout_seed`` (default ``seed``) and their
    weather and production drawn from ``seed``."""
    gen = SolarDataGenerator(n_days=n_days, seed=seed, start_day=start_day)
    layout = seed if layout_seed is None else layout_seed
    return [(s, gen.generate_site(s))
            for s in fleet_sites(n_sites, layout, n_regions, prefix)]


def make_windows(site_data: dict, stride: int = STEPS_PER_DAY) -> dict:
    """7-day history + next-day forecast -> 96 targets, one window per
    ``stride`` steps: ``history`` (n, 672, 10), ``forecast`` (n, 96, 9),
    ``target`` (n, 96)."""
    X = site_data["features"]
    y = site_data["production_norm"]
    T = len(y)
    starts = np.arange(0, T - HISTORY_STEPS - HORIZON_STEPS + 1, stride)
    hist, fore, targ = [], [], []
    for s in starts:
        h_end = s + HISTORY_STEPS
        f_end = h_end + HORIZON_STEPS
        hist.append(np.concatenate([X[s:h_end], y[s:h_end, None]], axis=1))
        fore.append(X[h_end:f_end])
        targ.append(y[h_end:f_end])
    return {"history": np.stack(hist).astype(np.float32),
            "forecast": np.stack(fore).astype(np.float32),
            "target": np.stack(targ).astype(np.float32)}


def split_windows(windows: dict, train_frac: float) -> tuple[dict, dict]:
    """Chronological train/test split (paper §IV.A)."""
    cut = int(len(windows["target"]) * train_frac)
    return ({k: v[:cut] for k, v in windows.items()},
            {k: v[cut:] for k, v in windows.items()})


def batch_iter(windows: dict, batch_size: int, rng: np.random.Generator):
    n = len(windows["target"])
    order = rng.permutation(n)
    for i in range(0, n, batch_size):
        sel = order[i:i + batch_size]
        yield {k: v[sel] for k, v in windows.items()}
